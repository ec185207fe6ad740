"""Traffic mixes: deterministic per seed, no scenario twice in a run."""

import itertools
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import generator  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
BIG_SEED = 2**31 + 12345


def _load(config, traffic):
    cfg = json.loads((ROOT / "bench" / "configs" /
                      f"{config}.json").read_text())
    tr = json.loads((ROOT / "bench" / "traffic" /
                     f"{traffic}.json").read_text())
    return cfg, tr


def _keys(calls, n):
    return [[s["key"] for s in call] for call in itertools.islice(calls, n)]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_same_seed_same_calls(config, traffic):
    cfg, tr = _load(config, traffic)
    a = _keys(generator.calls(cfg, tr, BIG_SEED), 30)
    b = _keys(generator.calls(cfg, tr, BIG_SEED), 30)
    c = _keys(generator.calls(cfg, tr, BIG_SEED + 1), 30)
    assert a == b
    assert a != c


@pytest.mark.parametrize("config,traffic", CELLS)
def test_no_scenario_repeats(config, traffic):
    cfg, tr = _load(config, traffic)
    keys = [k for call in _keys(generator.calls(cfg, tr, 7), 200)
            for k in call]
    assert len(keys) == len(set(keys))


def test_timing_offsets_stay_in_range():
    cfg, tr = _load("hitgraph-ddr3-yt", "timing-grid")
    for call in itertools.islice(generator.calls(cfg, tr, 3), 20):
        assert len(call) == len(tr["grades"])
        for s, grade in zip(call, tr["grades"]):
            for f, v in s["memory"]["timing"].items():
                assert v >= tr["min_cycles"]
                assert abs(v - grade["timing"][f]) <= 1 or v == 1


def test_design_points_cover_the_space_in_strata():
    cfg, tr = _load("hitgraph-ddr3-yt", "design-points")
    dims = cfg["design_points"]["dimensions"]
    runs = [list(generator.calls(cfg, tr, s)) for s in (5, 6, BIG_SEED)]
    for calls in runs:
        warm, window = calls[0], calls[1:]
        assert len(warm) == tr["warm_designs"]
        assert all(s["cache"] is None for s in warm)
        # every design of the classes once, each under every window cache
        designs = [c[0]["design"] for c in window]
        assert len(window) == len(tr["classes"]) * len(dims["pipelines"])
        assert all(len(c) == len(tr["window_caches"]) for c in window)
        assert all(len({s["design_key"] for s in c}) == len(c)
                   and all(s["design"] == c[0]["design"] for s in c)
                   for c in window)
        # the warm-up holds the window's first designs, and none of its
        # scenarios
        assert [s["design"] for s in warm] == designs[:len(warm)]
        keys = {s["key"] for c in window for s in c}
        assert not keys & {s["key"] for s in warm}
    # every seed runs the same sizes in the same order: only the seeded
    # axis differs
    sizes = [[{k: d["design"][k] for k in tr["classes"][0]}
              for d in (c[0] for c in calls[1:])] for calls in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    assert sizes[0][:len(tr["classes"])] == tr["classes"]
    seeded = [[c[0]["design"][tr["seeded"]] for c in calls[1:]]
              for calls in runs]
    assert seeded[0] != seeded[1]
