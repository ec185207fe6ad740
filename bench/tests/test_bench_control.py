"""The comparison that decides ``correct`` fails where it must: for the
reference's control in the program's place, and for a run whose timed
path is broken underneath (answers left out, an answer altered where it
is produced).  On the CPU at a small graph size; the look for a chip is
skipped, the rest of the run is the harness's own."""

import io
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

SCALE = 0.01
TIMING = "hitgraph-ddr3-yt.timing-grid"
POINTS = "hitgraph-ddr3-yt.design-points"


def run_small(cell, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(cell, 2**31 + 99, 1.0, False, require_tpu=False,
                      scale=SCALE, out=out, err=err, **kw)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def partitioned(monkeypatch):
    """The timing-grid design with several partitions, so that its small
    graph spreads over all four channels as the full-size one does."""
    real = run.cell

    def cell(name):
        bench, wl, config, traffic = real(name)
        config = dict(config, design=dict(config["design"],
                                          partition_elements=2048))
        return bench, wl, config, traffic

    monkeypatch.setattr(run, "cell", cell)


@pytest.mark.parametrize("cell", [TIMING, POINTS])
def test_control_is_not_correct(cell, partitioned):
    result = run_small(cell, control=True)
    assert result["correct"] is False
    assert result["checks"]["report_field_mismatches"]["value"] > 0


def test_program_is_correct_on_the_same_runs(partitioned):
    assert run_small(TIMING)["correct"] is True


def test_half_the_answers_left_out(monkeypatch):
    from repro.sim.sweep import Sweeper
    real = Sweeper.run

    def half(self, cases, **kw):
        rows = real(self, cases, **kw)
        return rows[:len(rows) // 2] + [None] * (len(rows) - len(rows) // 2)

    monkeypatch.setattr(Sweeper, "run", half)
    result = run_small(TIMING)
    assert result["correct"] is False
    assert result["checks"]["answers_missing"]["value"] > 0


@pytest.mark.parametrize("cell,fn", [(TIMING, "fused_scan_batch_shared"),
                                     (POINTS, "fused_scan")])
def test_answer_altered_where_served(monkeypatch, cell, fn):
    from repro.core import vectorized as vec
    real = getattr(vec, fn)

    def late(*a, **kw):
        fins, state = real(*a, **kw)
        return np.asarray(fins) + 1, state

    monkeypatch.setattr(vec, fn, late)
    result = run_small(cell)
    assert result["correct"] is False
    assert result["checks"]["runtime_ns_gap"]["value"] > 0


def test_algorithm_result_altered(monkeypatch):
    from repro.algorithms import edge_centric
    real = edge_centric.run

    def wrong(*a, **kw):
        res = real(*a, **kw)
        res.values[0] += 1
        return res

    monkeypatch.setattr(edge_centric, "run", wrong)
    result = run_small(TIMING)
    assert result["correct"] is False
    assert result["checks"]["label_mismatches"]["value"] > 0
