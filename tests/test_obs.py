"""The simulator's layer spans and counters (``repro.obs``): a profiled
``Sweeper.run`` records the span of every layer its path takes, serve
and reduce inside the sweep on the serving thread, and the serve's lane
counter counts exactly the lanes its dispatches ran."""

import sys
from pathlib import Path

import jax
import pytest

from repro import obs
from repro.core import vectorized as vec
from repro.sim.memory import timing_variants
from repro.sim.sweep import SweepCase, Sweeper

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spans as spans_mod  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

MEMORIES = timing_variants("ddr3", kinds=("ddr3", "ddr4", "hbm2"))
PATHS = {"per-case": dict(workers=2), "batched": dict(batch_memories=True)}


def _cases():
    return [SweepCase(graph="karate", problem="wcc", accelerator="hitgraph",
                      memory=m, cache="prefetch-4") for m in MEMORIES]


@pytest.fixture(scope="module", params=sorted(PATHS))
def profiled(request, tmp_path_factory):
    """One path's ``Sweeper.run`` under the profiler: its spans, its
    lane-counter delta, its rows and the sweeper."""
    sweeper = Sweeper(**PATHS[request.param])
    tdir = tmp_path_factory.mktemp(request.param)
    before = obs.counts()
    with jax.profiler.trace(str(tdir)):
        rows = sweeper.run(_cases())
    slots = obs.counts()["serve_lane_slots"] - before["serve_lane_slots"]
    spans = spans_mod.span_events(trace_mod.load(tdir))
    return request.param, spans, slots, rows, sweeper


def test_every_layer_of_the_path_has_its_span(profiled):
    _, spans, _, _, _ = profiled
    assert {n for _, _, n, _ in spans} == set(obs.SPANS)


def test_serve_and_reduce_lie_inside_the_sweep_on_its_line(profiled):
    _, spans, _, _, _ = profiled
    sweep, = [(s, e, line) for s, e, n, line in spans
              if n == obs.SWEEP_RUN]
    lo, hi, line = sweep
    for name in (obs.SERVE, obs.REDUCE):
        inner = [(s, e) for s, e, n, ln in spans if n == name]
        assert inner
        assert all(ln == line and lo <= s and e <= hi
                   for s, e, n, ln in spans if n == name)
    # the preparation ran on worker threads, not on the serving line
    assert {ln for _, _, n, ln in spans if n == obs.PACK} - {line}


def test_lane_slots_count_the_dispatched_lanes(profiled):
    path, _, slots, rows, sweeper = profiled
    packs = [sweeper._prepare_case(c)[2] for c in _cases()]
    assert len({id(p) for p in packs}) == 1        # one geometry, one pack
    S, C, K = packs[0].issue.shape
    M = len(packs)
    assert slots == sum(vec.plan_chunks(packs[0].n_steps)) * C * K * M
    assert slots >= sum(r.report.total_requests for r in rows)


def test_dispatch_counts_keep_their_five_keys():
    assert set(vec.dispatch_counts()) == {
        "packed", "fused", "fused_batch", "device_pack", "pallas"}
    assert set(obs.counts()) == set(vec.dispatch_counts()) | {
        "serve_lane_slots", "pack_requests", "pack_slots"}


def test_counters_count_and_reset():
    obs.count("serve_lane_slots", 5)
    vec.count_dispatch("fused", 2)
    assert obs.counts()["serve_lane_slots"] >= 5
    assert vec.dispatch_counts()["fused"] >= 2
    vec.reset_dispatch_counts()
    assert set(obs.counts().values()) == {0}
    with pytest.raises(KeyError):
        obs.count("no-such-counter")
