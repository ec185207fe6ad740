"""The reduction from trace events to the benchmark's numbers."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench import trace as T  # noqa: E402

LAYERS = {k: v for k, v in
          json.loads((ROOT / "bench" / "layers.json").read_text()).items()
          if k != "about"}
MS = 1_000_000


def _events():
    """A small trace in the form ``trace.events`` returns: one chip, a
    batched serve program, a pack program, and host spans (ns)."""
    return {
        "devices": {"/device:TPU:0": [
            (10 * MS, 25 * MS, "jit__fused_scan_batch_shared(7)"),
            (24 * MS, 40 * MS, "jit__fused_scan_batch_shared(7)"),
            (50 * MS, 60 * MS, "jit__device_pack_core(3)"),
            (70 * MS, 75 * MS, "jit_concatenate(9)")]},
        "host": [(0, 100 * MS, "python:bench.window"),
                 (40 * MS, 50 * MS, "python:PjitFunction(_device_pack_core)"),
                 (41 * MS, 49 * MS, "python:model build"),
                 (60 * MS, 70 * MS, "python:bench.call")],
    }


def test_union_and_gaps():
    assert T.union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert T.union_length([(0, 10), (2, 3)]) == 10
    assert T.gaps([(10, 20), (15, 30)], 0, 40) == [(0, 10), (30, 40)]
    assert T.gaps([], 5, 7) == [(5, 7)]


def test_summary_busy_layers_and_gaps():
    ev = _events()
    s = T.summarize(ev, T.host_span(ev, "bench.window"), LAYERS)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.045)          # 30 + 10 + 5 ms
    assert s["layer_s"]["serve"] == pytest.approx(0.031)
    assert s["layer_s"]["pack"] == pytest.approx(0.010)
    assert s["layer_s"]["cache_filter"] == 0.0
    assert s["layer_programs"]["serve"] == [
        "jit__fused_scan_batch_shared(7)"]
    assert s["layer_programs"]["cache_filter"] == []
    # longest idle gap first, named by the shortest host span over it
    assert s["idle_gaps"][0] == ["python:bench.window", pytest.approx(0.025)]
    named = dict(s["idle_gaps"])
    assert named["python:model build"] == pytest.approx(0.010)
    assert named["python:bench.call"] == pytest.approx(0.010)
    top = dict(s["device_ops"])
    assert top["jit__fused_scan_batch_shared"] == pytest.approx(0.031)
    assert list(top)[0] == "jit__fused_scan_batch_shared"


def test_window_clips_events():
    ev = _events()
    s = T.summarize(ev, (20 * MS, 55 * MS), LAYERS)
    assert s["busy_s"] == pytest.approx(0.025)
    assert s["layer_s"]["serve"] == pytest.approx(0.021)


def test_metric_readers_and_roofline():
    ev = _events()
    summary = T.summarize(ev, T.host_span(ev, "bench.window"), LAYERS)
    ctx = {"trace": summary,
           "window": {"seconds": 0.1, "scenarios": 4, "requests": 2_000_000,
                      "serve_dispatches": 6},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert run.reader("device_idle_pct")(ctx) == pytest.approx(55.0)
    assert run.reader("serve_ms_per_mreq")(ctx) == pytest.approx(15.5)
    assert run.reader("pack_ms_per_scenario")(ctx) == pytest.approx(2.5)
    assert run.reader("serve_dispatches_per_scenario")(ctx) == 1.5
    # 2M requests x 12 bytes at 819 GB/s over 31 ms of serve
    assert run.reader("serve_roofline")(ctx) == pytest.approx(
        100 * 2e6 * 12 / 819e9 / 0.031)


def test_readers_find_nothing_without_their_layer():
    ev = _events()
    ev["devices"]["/device:TPU:0"] = [(0, MS, "jit_add(1)")]
    summary = T.summarize(ev, (0, 100 * MS), LAYERS)
    ctx = {"trace": summary,
           "window": {"seconds": 0.1, "scenarios": 4, "requests": 10,
                      "serve_dispatches": 0},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("serve_ms_per_mreq", "serve_roofline",
                 "pack_ms_per_scenario"):
        assert run.reader(name)(ctx) is None


def test_recorded_host_trace(tmp_path):
    """A trace recorded by the JAX profiler here (host only) holds the
    harness's annotations where ``host_span`` looks for them."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(4096)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = T.events(T.load(tmp_path))
    lo, hi = T.host_span(ev, "bench.window")
    assert hi > lo
    with pytest.raises(LookupError):
        T.host_span(ev, "bench.nothing")
