"""The reduction of the program's host spans: self time per host line and
the device's idle time by span, to the nanosecond."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spans as S  # noqa: E402

MS = 1_000_000


def _events():
    """One chip busy at [10, 40], [50, 60] and [70, 75] ms of a 100 ms
    window; the serving thread (line 0) and a preparation worker (line
    1) with nested spans, each line with a device wait."""
    return {
        "devices": {"/device:TPU:0": [
            (10 * MS, 25 * MS, "jit__fused_scan_batch_shared(7)"),
            (24 * MS, 40 * MS, "jit__fused_scan_batch_shared(7)"),
            (50 * MS, 60 * MS, "jit__device_pack_core(3)"),
            (70 * MS, 75 * MS, "jit_concatenate(9)")]},
        "host": [(0, 100 * MS, "python:bench.window")],
        "spans": [
            (5 * MS, 95 * MS, "repro.sweep.run", 0),
            (8 * MS, 45 * MS, "repro.wait_prepare", 0),
            (55 * MS, 68 * MS, "repro.serve", 0),
            (62 * MS, 66 * MS, "repro.device_wait", 0),
            (68 * MS, 72 * MS, "repro.reduce", 0),
            (9 * MS, 30 * MS, "repro.trace_build", 1),
            (30 * MS, 48 * MS, "repro.pack", 1),
            (31 * MS, 33 * MS, "repro.device_wait", 1),
        ],
    }


def _ns(seconds):
    return round(seconds * 1e9)


def test_self_time_per_line():
    s = S.summarize(_events(), (0, 100 * MS))["spans"]
    got = {n: (t["count"], _ns(t["total_s"]), _ns(t["self_s"]))
           for n, t in s.items()}
    assert got == {
        # less wait_prepare, serve and reduce on its own line; the
        # worker's spans under it in time are not its children
        "repro.sweep.run": (1, 90 * MS, 36 * MS),
        "repro.wait_prepare": (1, 37 * MS, 37 * MS),
        "repro.serve": (1, 13 * MS, 9 * MS),
        "repro.reduce": (1, 4 * MS, 4 * MS),
        "repro.trace_build": (1, 21 * MS, 21 * MS),
        "repro.pack": (1, 18 * MS, 16 * MS),
        "repro.device_wait": (2, 6 * MS, 6 * MS),
    }


def test_idle_goes_to_the_shortest_covering_span():
    idle = S.summarize(_events(), (0, 100 * MS))["idle_by_span"]
    assert {n: _ns(v) for n, v in idle.items()} == {
        # [0, 5] and [95, 100]: under no span
        "(none)": 10 * MS,
        # [5, 8], [48, 50] and [75, 95]
        "repro.sweep.run": 25 * MS,
        # [8, 9]: until the worker's trace build starts
        "repro.wait_prepare": 1 * MS,
        "repro.trace_build": 1 * MS,
        # the gap [40, 50] straddles the pack and the sweep
        "repro.pack": 8 * MS,
        # the gap [60, 70] straddles serve, its wait and the reduce
        "repro.serve": 4 * MS,
        "repro.device_wait": 4 * MS,
        "repro.reduce": 2 * MS,
    }
    assert _ns(sum(idle.values())) == 55 * MS     # 100 - 45 busy


def test_window_clips_spans_and_idle():
    s = S.summarize(_events(), (20 * MS, 65 * MS))
    assert _ns(s["spans"]["repro.sweep.run"]["total_s"]) == 45 * MS
    # 45 less wait_prepare [20, 45] and serve [55, 65]
    assert _ns(s["spans"]["repro.sweep.run"]["self_s"]) == 10 * MS
    assert _ns(s["spans"]["repro.trace_build"]["total_s"]) == 10 * MS
    assert {n: _ns(v) for n, v in s["idle_by_span"].items()} == {
        "repro.pack": 8 * MS, "repro.sweep.run": 2 * MS,
        "repro.serve": 2 * MS, "repro.device_wait": 3 * MS}


def test_no_device_leaves_the_whole_window_idle():
    ev = _events()
    ev["devices"] = {}
    idle = S.summarize(ev, (0, 100 * MS))["idle_by_span"]
    assert _ns(sum(idle.values())) == 100 * MS
    assert _ns(idle["(none)"]) == 10 * MS


def test_window_of():
    ev = _events()
    assert S.window_of(ev) == (0, 100 * MS)
    ev["host"] = []
    assert S.window_of(ev) == (5 * MS, 95 * MS)
    ev["spans"] = []
    with pytest.raises(LookupError):
        S.window_of(ev)
