"""The harness end to end on the CPU at a small graph size: it refuses to
run without a TPU, and every cell's run comes out correct."""

import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SCALE = 0.01


def run_small(cell, seed=2**31 + 7, seconds=1.0, trace=False, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(cell, seed, seconds, trace, require_tpu=False,
                      scale=SCALE, out=out, err=err, **kw)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), err.getvalue()


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(cell):
    rc, result, err = run_small(cell)
    assert rc == 0
    assert result["correct"] is True, err
    assert result["failed"] == 0
    assert result["attempted"] >= 1
    names = {m["name"] for m in run.cell_metrics(BENCH, cell, "end_to_end")}
    assert set(result["metrics"]) == names
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    # every compared number is on the last lines of standard error
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [t.split(":")[0] for t in tail] == [
        f"check {k}" for k in result["checks"]]
