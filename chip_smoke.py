"""Smoke run of the simulator's main path on TPU chips.

    python chip_smoke.py             # one chip: phases 1-5 below
    python chip_smoke.py --chips 4   # four chips: the sharded sweep only

Everything runs in this one process, through the public entry points,
on the ``youtube`` stand-in of the paper's Tab. 1 at full size
(1,157,828 vertices; 5,975,248 undirected edge entries), generated from
seed 0.

1. Print the JAX version and the devices; anything but a TPU exits 1.
2. Three scenarios with ``serve_backend`` auto, scan and pallas, each
   report equal to ``simulate()`` with the XLA scan on the host's CPU
   device in this process.
3. Replay the small-graph golden digests (``tests/goldens``).
4. ``sweep(batch_memories=True)`` over a 12-grade timing grid on one
   pack, each row equal to ``SimSession.run`` (the engine under
   ``simulate()``).
5. One ``SimService`` submit/result, equal to ``simulate()``.

With ``--chips 4`` only the timing-grid sweep runs, with ``devices=4``
against ``devices=1``, rows equal.

Each phase prints its wall time, the JAX compile time inside it and the
serve dispatches it made.  Any mismatch or exception exits non-zero;
only a run where everything matched ends with the JSON device line.
Where ``JAX_COMPILATION_CACHE_DIR`` is not set, compiled programs are
cached in ``.jax_cache`` beside this file.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

GRAPH = "yt"
#: (label, accelerator, memory) of the three full-size scenarios: the
#: paper's HitGraph on 4 DDR3 channels and AccuGraph on 1 DDR4 channel,
#: and the widest channel count a preset ships (16-channel HBM2E)
SCENARIOS = (("hitgraph/ddr3", "hitgraph", "ddr3"),
             ("accugraph/ddr4", "accugraph", "ddr4"),
             ("hitgraph/hbm2e", "hitgraph", "hbm2e"))
BACKENDS = ("auto", "scan", "pallas")
#: the ``dispatch_counts`` key each backend must move on the TPU
SERVED_BY = {"auto": "pallas", "scan": "fused", "pallas": "pallas"}

#: JAX's lowering and XLA/Mosaic compile events (tracing is left out:
#: nested jits report overlapping trace spans)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_COMPILE_S = [0.0]


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        _COMPILE_S[0] += duration


class Mismatch(AssertionError):
    pass


@contextlib.contextmanager
def phase(label):
    """Print the block's wall time, the compile time inside it and the
    serve dispatches it made; the yielded dict receives the dispatches."""
    from repro.core import vectorized as vec
    c0, d0 = _COMPILE_S[0], vec.dispatch_counts()
    dispatches = {}
    t0 = time.perf_counter()
    yield dispatches
    wall = time.perf_counter() - t0
    compile_s = _COMPILE_S[0] - c0
    d1 = vec.dispatch_counts()
    dispatches.update({k: d1[k] - d0[k] for k in d1 if d1[k] != d0[k]})
    print(f"[{label}] wall_s={wall:.3f} compile_s={compile_s:.3f} "
          f"run_s={wall - compile_s:.3f} dispatches={dispatches}",
          flush=True)


def _summary(r):
    return (f"iterations={r.iterations} requests={r.total_requests} "
            f"runtime_ns={r.runtime_ns!r} row_hit_rate={r.row_hit_rate!r} "
            f"phases={len(r.phases)}")


def check(label, got, want):
    if got != want:
        raise Mismatch(f"{label}: {_summary(got)} != {_summary(want)}")


def build_graph():
    from repro.graphs.datasets import instantiate
    with phase("graph"):
        g = instantiate(GRAPH, scale=1.0, seed=0).undirected_view()
    print(f"graph {g.name}: vertices={g.n} edge_entries={g.m}", flush=True)
    return g


def timing_grid():
    """HitGraph/DDR3 geometry with twelve speed grades' timing."""
    from repro.sim import timing_variants
    from repro.sim.memory import TIMING_PRESETS
    kinds = ("ddr3", "ddr4", "hbm2", "hbm2e") + tuple(TIMING_PRESETS)
    return timing_variants("ddr3", kinds=kinds)


def grid_sweep(g, devices):
    from repro.sim import ScenarioSpec, Sweeper, sweep
    cases = [ScenarioSpec(g, "wcc", accelerator="hitgraph", memory=m)
             for m in timing_grid()]
    sweeper = Sweeper(batch_memories=True, devices=devices)
    with phase(f"sweep devices={devices} cases={len(cases)}"):
        rows = sweep(cases=cases, sweeper=sweeper)
    print(f"sweep stats: {sweeper.stats}", flush=True)
    return rows, sweeper


def scenarios(g):
    """Phase 2: every backend on the chip against the CPU scan.

    The three backends of a scenario share one ``SimSession`` (what
    ``simulate()`` runs), so the algorithm runs once per scenario: the
    AccuGraph relaxation is a sequential per-edge scan that takes minutes
    on the chip.  One fresh ``simulate()`` checks the session path."""
    from repro.sim import ScenarioSpec, SimSession, simulate
    cpu = jax.devices("cpu")[0]
    reports = {}
    for label, accel, memory in SCENARIOS:
        spec = ScenarioSpec(g, "wcc", accelerator=accel, memory=memory)
        with phase(f"{label} cpu scan reference"), jax.default_device(cpu):
            want = simulate(spec, serve_backend="scan")
        print(f"{label}: {_summary(want)}", flush=True)
        sess = SimSession(g)
        for backend in BACKENDS:
            with phase(f"{label} serve_backend={backend}") as dispatched:
                got = sess.run("wcc", accel, memory=memory,
                               serve_backend=backend)
            served = SERVED_BY[backend]
            if not dispatched.get(served):
                raise Mismatch(f"{label} {backend}: no {served!r} serve "
                               f"dispatch, got {dispatched}")
            check(f"{label} {backend} vs cpu scan", got, want)
        reports[label] = want
    label, accel, memory = SCENARIOS[0]
    with phase(f"{label} simulate()"):
        got = simulate(ScenarioSpec(g, "wcc", accelerator=accel,
                                    memory=memory))
    check(f"{label} simulate() vs cpu scan", got, reports[label])
    return reports


def goldens():
    """Phase 3: the repo's own golden digests, replayed on the chip."""
    sys.path.insert(0, str(REPO / "tests"))
    import test_goldens
    want = json.loads(test_goldens.GOLDEN_PATH.read_text())
    with phase(f"goldens n={len(want)}"):
        got = test_goldens._collect()
    if set(got) != set(want):
        raise Mismatch(f"golden keys differ: {sorted(set(got) ^ set(want))}")
    bad = [k for k in sorted(want) if got[k] != want[k]]
    if bad:
        raise Mismatch(f"{len(bad)} golden digests differ, first {bad[0]}: "
                       f"{got[bad[0]]} != {want[bad[0]]}")
    print(f"goldens: {len(want)} digests match", flush=True)


def timing_sweep(g, base):
    """Phase 4: the batched timing grid against per-case runs."""
    from repro.core import vectorized as vec
    from repro.sim import SimSession
    fused_batch = vec.dispatch_counts()["fused_batch"]
    rows, _ = grid_sweep(g, devices=1)
    if vec.dispatch_counts()["fused_batch"] == fused_batch:
        raise Mismatch("the timing grid was not served batched")
    check("sweep ddr3-timing row vs simulate", rows[0].report, base)
    sess = SimSession(g)
    with phase(f"sweep references n={len(rows)}"):
        for row in rows:
            check(f"sweep row {row.memory}", row.report,
                  sess.run("wcc", "hitgraph", memory=row.case.memory))
    print(f"sweep: {len(rows)} rows match", flush=True)


def service(g, base):
    """Phase 5: one job through the service."""
    from repro.serve.engine import SimService
    from repro.sim import ScenarioSpec
    svc = SimService()
    try:
        with phase("service submit/result"):
            job = svc.submit(ScenarioSpec(g, "wcc", accelerator="hitgraph",
                                          memory="ddr3"))
            rows = svc.result(job, timeout=600)
    finally:
        svc.close()
    check("service row vs simulate", rows[0].report, base)
    print("service: row matches", flush=True)


def four_chips(g):
    """The sharded timing-grid sweep against the one-device sweep."""
    one, _ = grid_sweep(g, devices=1)
    four, sweeper = grid_sweep(g, devices=4)
    mesh = sweeper._sweep_mesh()
    used = sorted(d.id for d in mesh.devices.flat)
    if len(used) != 4 or sweeper.stats.sharded_dispatches == 0:
        raise Mismatch(f"cases were not sharded over 4 devices: mesh {used}, "
                       f"{sweeper.stats.sharded_dispatches} sharded "
                       "dispatches")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    print(f"mesh devices {used}, peak bytes in use per device {peaks}",
          flush=True)
    for a, b in zip(one, four):
        check(f"devices=4 row {a.memory}", b.report, a.report)
    print(f"sharded sweep: {len(four)} rows equal to devices=1",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    d = devices[0]
    print(f"jax {jax.__version__} platform={d.platform} "
          f"device_kind={d.device_kind} device_count={len(devices)}",
          flush=True)
    if d.platform != "tpu":
        print("no TPU: this smoke run needs the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    t0 = time.perf_counter()
    g = build_graph()
    if args.chips == 4:
        four_chips(g)
    else:
        reports = scenarios(g)
        goldens()
        timing_sweep(g, reports["hitgraph/ddr3"])
        service(g, reports["hitgraph/ddr3"])
    print(f"total wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={_COMPILE_S[0]:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
