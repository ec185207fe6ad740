"""Run one benchmark cell once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  One process holds one resident
``Sweeper`` (the engine under ``sweep()`` and ``SimService``) and sends
it ``Sweeper.run`` calls in a closed loop from one client:

1. set-up: make the configuration's graph (from its own fixed seed),
   build the sweeper, send the warm-up call (algorithm, model, trace,
   pack and every compiled shape of the cell's traffic; where the
   traffic says ``warm_apart``, on a sweeper of its own, and the
   window's sweeper runs only the algorithm);
2. window: whole calls until ``--seconds`` have passed, then the call in
   flight finishes; rates are taken over all scenarios the calls
   completed and the time from the window's start to the last call's
   end, and the end-to-end metrics are printed.  With ``--trace 1`` the
   window is the first call alone, run under the JAX profiler, and the
   per-layer metrics (``bench/metrics/<name>.py``) are read from its
   trace;
3. check: a sample of the window's scenarios, drawn from the seed with
   the longest among them, is computed again by the plain reference of
   the configuration (``bench/reference``) and compared field by field.

Standard error ends with every compared number beside its limit; the
last line of standard output is the result as one JSON object.  A run
that finds no TPU, or fewer chips than the cell asks for, exits 2 and
prints no result.  Compiled programs are cached in
``JAX_COMPILATION_CACHE_DIR`` or, where that is unset, in ``.jax_cache``
at the root of the checkout.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import generator, graphs  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts JAX lowerings, backend compiles and persistent-cache hits
    (a cache hit also reports a backend-compile duration, so compiles
    are the difference) and their seconds."""

    def __init__(self):
        self.lowerings = self.backend = self.hits = 0
        self.seconds = 0.0

    def on_duration(self, event, duration, **_):
        if event == LOWER_EVENT:
            self.lowerings += 1
            self.seconds += duration
        elif event == COMPILE_EVENT:
            self.backend += 1
            self.seconds += duration

    def on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def snapshot(self):
        return (self.lowerings, self.backend - self.hits, self.hits,
                self.seconds)


def load_json(path: Path):
    return json.loads(path.read_text())


def cell(workload: str):
    """``(benchmark, workload entry, config, traffic)`` of a cell."""
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = load_json(ROOT / cfg["file"])
    traffic = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    return bench, wl, config, traffic


def cell_metrics(bench, workload: str, kind: str):
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics this cell
    reports: those that list it, or that list no cells."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def device_info(jax, chips: int):
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def sample(done, rng, k: int):
    """``k`` completed scenarios drawn from the seed, the one with the
    most simulated requests always among them."""
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: done[i][2].report.total_requests)
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def compare(got: dict, want: dict):
    """Fields of ``got`` that differ from ``want`` (phases one by
    one), and the gap in ``runtime_ns``."""
    bad = [k for k in want if k != "phases" and got[k] != want[k]]
    gp, wp = got["phases"], want["phases"]
    bad += [f"phases[{i}]" for i in range(max(len(gp), len(wp)))
            if i >= len(gp) or i >= len(wp) or gp[i] != wp[i]]
    return bad, abs(got["runtime_ns"] - want["runtime_ns"])


def algorithm_results(picked, sweeper):
    """The program's algorithm result for each distinct algorithm run
    among the sampled scenarios."""
    from bench import program
    values = {}
    for _, case, _ in picked:
        key = (case.accelerator, case.problem,
               getattr(case.config, "partition_elements", None))
        if key not in values:
            values[key] = np.asarray(program.algorithm_values(sweeper, case))
    return list(values.values())


def check(picked, values, missing, graph, config, err, control=False):
    """Compare the sampled answers with the reference.  Returns
    ``(checks, failed)``; ``checks`` maps each compared number's name to
    ``{"value", "limit"}``."""
    import jax

    from bench import program
    t0 = time.perf_counter()
    # on the host's CPU: apart from the chip under test, and it leaves
    # the device's memory as the program's run left it
    with jax.default_device(jax.devices("cpu")[0]):
        labels, want = reference(config["reference"]).run(
            graph, config, [p[0] for p in picked], control=control)
    fields_bad = 0
    gap = 0.0
    failed = 0
    for scenario, _, row in picked:
        bad, g = compare(program.report_fields(row.report),
                         want[scenario["key"]])
        if bad:
            failed += 1
            print(f"mismatch {scenario['key']}: {bad[:8]}", file=err)
        fields_bad += len(bad)
        gap = max(gap, g)
    print(f"reference: {len(picked)} scenarios in "
          f"{time.perf_counter() - t0:.3f} s", file=err)
    checks = {
        "scenarios_unchecked": {"value": 0 if picked else 1, "limit": 0},
        "answers_missing": {"value": missing, "limit": 0},
        "label_mismatches": {
            "value": sum(int((v != labels).sum()) for v in values),
            "limit": 0},
        "report_field_mismatches": {"value": fields_bad, "limit": 0},
        "runtime_ns_gap": {"value": gap, "limit": 0},
    }
    return checks, failed + missing


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, scale: float = 1.0,
             control: bool = False, out=None, err=None) -> int:
    """One run of one cell; returns the exit code.  ``scale`` shrinks
    the graph, ``require_tpu=False`` skips the look for a chip, and
    ``control`` puts the reference's control in the comparison: all
    three are for the benchmark's own tests and control runs."""
    out = out or sys.stdout
    err = err or sys.stderr
    bench, wl, config, traffic = cell(workload)
    import jax

    from bench import program
    devices = jax.devices()
    d0 = devices[0]
    print(f"platform={d0.platform} device_kind={d0.device_kind} "
          f"device_count={len(devices)}", file=err, flush=True)
    if require_tpu and (d0.platform != "tpu" or len(devices) < wl["chips"]):
        print(f"this cell needs {wl['chips']} TPU chip(s); found "
              f"{len(devices)} {d0.platform} device(s)", file=err)
        return 2
    peaks = load_json(BENCH / "peaks.json")["devices"].get(d0.device_kind)
    if require_tpu:
        if peaks is None:
            raise SystemExit(f"no peaks for device kind {d0.device_kind!r} "
                             "in bench/peaks.json")
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(
        counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)

    # ---- set-up --------------------------------------------------------
    graph = graphs.make(config["graph"], scale)
    g = program.program_graph(graph)
    graph_s = time.perf_counter() - PROCESS_START
    sweeper = program.make_sweeper(traffic)
    calls = generator.calls(config, traffic, seed)
    warm = [program.sweep_case(g, config, s) for s in next(calls)]
    if traffic.get("warm_apart"):
        # compile on a sweeper of its own, so that the window's sweeper
        # holds nothing of the warm-up but the algorithm's result
        program.make_sweeper(traffic).run(warm)
        gc.collect()
        program.algorithm_values(sweeper, warm[0])
    else:
        sweeper.run(warm)
    setup_s = time.perf_counter() - PROCESS_START
    print(f"setup_s={setup_s:.3f} (graph ready at {graph_s:.3f} s; "
          f"warm-up {len(warm)} scenarios) graph vertices={graph['n']} "
          f"edge_entries={len(graph['src'])}", file=err, flush=True)

    # ---- window --------------------------------------------------------
    tdir = BENCH / ".trace" / f"{workload}-{graphs.seed_value(seed)}"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # annotations and jit events only
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    c0 = counter.snapshot()
    d_start = program.dispatch_counts()
    done = []
    missing = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for i, call in enumerate(calls):
            cases = [program.sweep_case(g, config, s) for s in call]
            tc, cc = time.perf_counter(), counter.snapshot()
            with jax.profiler.TraceAnnotation("bench.call"):
                rows = sweeper.run(cases)
            for s, c, r in zip(call, cases, rows):
                if r is None:
                    missing += 1
                else:
                    done.append((s, c, r))
            missing += max(len(call) - len(rows), 0)
            print(f"call {i}: {len(rows)} scenarios "
                  f"{sum(r.report.total_requests for r in rows if r)} "
                  f"requests {time.perf_counter() - tc:.3f} s compiles="
                  f"{counter.snapshot()[1] - cc[1]}", file=err, flush=True)
            # a traced run traces one call: a serve scan leaves every op
            # of every step in the trace
            if trace or time.perf_counter() - t0 >= seconds:
                break
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    c1 = counter.snapshot()
    d_end = program.dispatch_counts()
    window_s = t1 - t0
    requests = sum(r.report.total_requests for _, _, r in done)
    print(f"window: {len(done)} scenarios, {requests} requests in "
          f"{window_s:.3f} s", file=err)
    print(f"window compiles={c1[1] - c0[1]} cache_loads={c1[2] - c0[2]} "
          f"lowerings={c1[0] - c0[0]} "
          f"compile_s={c1[3] - c0[3]:.3f}", file=err, flush=True)
    device = device_info(jax, wl["chips"])
    window = {
        "seconds": window_s, "scenarios": len(done), "requests": requests,
        "serve_dispatches": sum(d_end[k] - d_start[k]
                                for k in program.SERVE_DISPATCHES),
    }

    # ---- metrics -------------------------------------------------------
    metrics = {}
    breakdown = None
    if trace:
        tt = time.perf_counter()
        ev = trace_mod.events(trace_mod.load(tdir))
        print(f"trace: stopped and read in {time.perf_counter() - t1:.3f} s "
              f"(read {time.perf_counter() - tt:.3f} s)", file=err)
        span = trace_mod.host_span(ev, "bench.window")
        layers = {k: v for k, v in load_json(BENCH / "layers.json").items()
                  if k != "about"}
        summary = trace_mod.summarize(ev, span, layers)
        shutil.rmtree(tdir, ignore_errors=True)
        print(f"trace: busy_s={summary['busy_s']:.6f} "
              f"window_s={summary['window_s']:.6f} "
              f"layer_s={summary['layer_s']} "
              f"programs={summary['layer_programs']}", file=err)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        ctx = {"window": window, "trace": summary, "peaks": peaks}
        for m in cell_metrics(bench, workload, "per_layer"):
            value = reader(m["name"])(ctx)
            if value is None:
                raise RuntimeError(
                    f"per-layer metric {m['name']} found nothing to read "
                    f"in {workload}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {
            "scenarios_per_s": len(done) / window_s,
            "sim_mreq_per_s": requests / 1e6 / window_s,
            "setup_s": setup_s,
        }
        for m in cell_metrics(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # ---- check ---------------------------------------------------------
    rng = np.random.default_rng([graphs.seed_value(seed), 3])
    picked = sample(done, rng, int(traffic["check_scenarios"]))
    values = algorithm_results(picked, sweeper)
    del sweeper, done
    gc.collect()
    checks, failed = check(picked, values, missing, graph, config, err,
                           control=control)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    result = {"correct": correct, "attempted": window["scenarios"] + missing,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
