"""Every cell of BENCHMARK.json resolves to its files, and the file keeps
to the benchmark contract's shape."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    from bench import run
    bench, wl, config, traffic = run.cell(cell)
    assert config["name"] == wl["config"]
    assert config["reduced"] == [
        c for c in bench["configs"] if c["name"] == wl["config"]][0][
        "reduced"]
    assert traffic["generator"]
    assert (ROOT / "bench" / "reference" /
            f"{config['reference']}.py").is_file()
    e2e = run.cell_metrics(bench, cell, "end_to_end")
    assert {m["name"] for m in e2e} >= {"setup_s"} and len(e2e) >= 2
    per_layer = run.cell_metrics(bench, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert callable(run.reader(m["name"]))
        assert m["moves"] in {x["name"] for x in e2e}


def test_per_layer_workloads_exist():
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS, (m["name"], w)


def test_every_config_is_used_and_has_its_file():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert data["assumed"]


def test_peaks_name_their_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
