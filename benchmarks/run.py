"""Benchmark harness: one entry per paper table/figure + kernels.

Prints ``name,us_per_call,derived`` CSV per the harness contract, and a
per-suite summary on stderr.  ``--scale`` shrinks/grows the dataset
stand-ins (default 1% of Tab. 1 sizes).

Running the ``sweep`` suite also appends one trajectory row (date, scale,
cases/sec per variant) to ``BENCH_sweep.json`` at the repo root, so the
sweep-throughput perf figure is tracked across PRs; CI uploads the file
as an artifact and fails on >2x regression vs
``benchmarks/baselines/sweep_throughput.json``.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
BENCH_SWEEP_PATH = REPO_ROOT / "BENCH_sweep.json"
BENCH_SERVICE_PATH = REPO_ROOT / "BENCH_service.json"
BENCH_TUNE_PATH = REPO_ROOT / "BENCH_tune.json"
BENCH_DYNAMIC_PATH = REPO_ROOT / "BENCH_dynamic.json"


def append_sweep_trajectory(sweep_rows, scale: float,
                            path: Path = BENCH_SWEEP_PATH) -> dict:
    """Append one {date, scale, <variant>_cases_per_sec...} row to the
    append-style trajectory file (a JSON list; one entry per recorded
    run).  ``REPRO_BENCH_HOST`` (CI sets ``github-actions``) tags the
    row with its machine class so the regression gate only ever
    compares like-for-like hardware."""
    entry = {
        "date": datetime.date.today().isoformat(),
        "scale": scale,
    }
    host = os.environ.get("REPRO_BENCH_HOST")
    if host:
        entry["host"] = host
    for r in sweep_rows:
        if r.get("bench") != "sweep":
            continue
        entry[f"{r['variant']}_cases_per_sec"] = round(
            r["cases_per_sec"], 3)
        if "workers" in r:
            entry.setdefault("workers", r["workers"])
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(entry)
    path.write_text(json.dumps(history, indent=1) + "\n")
    return entry


def append_service_trajectory(service_rows, scale: float,
                              path: Path = BENCH_SERVICE_PATH) -> dict:
    """Append one {date, scale, <variant>_cases_per_sec / latency /
    recovery counters} row to ``BENCH_service.json`` (same append-style
    trajectory + host tagging as the sweep figure; the CI gate compares
    ``clean_cases_per_sec`` like-for-like)."""
    entry = {
        "date": datetime.date.today().isoformat(),
        "scale": scale,
    }
    host = os.environ.get("REPRO_BENCH_HOST")
    if host:
        entry["host"] = host
    for r in service_rows:
        if r.get("bench") != "service":
            continue
        v = r["variant"]
        entry[f"{v}_cases_per_sec"] = round(r["cases_per_sec"], 3)
        entry[f"{v}_latency_p50_ms"] = round(r["latency_p50_ms"], 1)
        entry[f"{v}_latency_p99_ms"] = round(r["latency_p99_ms"], 1)
        entry.setdefault("workers", r.get("workers"))
        if v == "faulted":
            for k in ("shed", "retries", "quarantined",
                      "worker_crashes", "injected"):
                if k in r:
                    entry[f"faulted_{k}"] = r[k]
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(entry)
    path.write_text(json.dumps(history, indent=1) + "\n")
    return entry


def append_tune_trajectory(tune_rows, scale: float,
                           path: Path = BENCH_TUNE_PATH) -> dict:
    """Append one {date, scale, tune_cases_per_sec, front_size...} row
    to ``BENCH_tune.json`` (same append-style trajectory + host tagging
    as the sweep figure; the CI gate compares ``tune_cases_per_sec``
    like-for-like via ``check_regression.py --keys``)."""
    entry = {
        "date": datetime.date.today().isoformat(),
        "scale": scale,
    }
    host = os.environ.get("REPRO_BENCH_HOST")
    if host:
        entry["host"] = host
    for r in tune_rows:
        if r.get("bench") != "tune":
            continue
        v = r["variant"]
        entry[f"{v}_cases_per_sec"] = round(r["cases_per_sec"], 3)
        entry[f"{v}_front_size"] = r["front_size"]
        entry.setdefault("workers", r.get("workers"))
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(entry)
    path.write_text(json.dumps(history, indent=1) + "\n")
    return entry


def append_dynamic_trajectory(dynamic_rows, scale: float,
                              path: Path = BENCH_DYNAMIC_PATH) -> dict:
    """Append one {date, scale, dynamic_epochs_per_sec,
    locality_advantage...} row to ``BENCH_dynamic.json`` (same
    append-style trajectory + host tagging as the sweep figure; the CI
    gate compares ``dynamic_epochs_per_sec`` like-for-like)."""
    entry = {
        "date": datetime.date.today().isoformat(),
        "scale": scale,
    }
    host = os.environ.get("REPRO_BENCH_HOST")
    if host:
        entry["host"] = host
    for r in dynamic_rows:
        if r.get("bench") != "dynamic":
            continue
        if r["variant"] == "sweep":
            entry["dynamic_epochs_per_sec"] = round(
                r["dynamic_epochs_per_sec"], 3)
            entry["epochs"] = r["epochs"]
            entry["cases"] = r["cases"]
        elif r["variant"] == "locality":
            entry["locality_advantage"] = round(
                r["locality_advantage"], 4)
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except json.JSONDecodeError:
            history = []
    history.append(entry)
    path.write_text(json.dumps(history, indent=1) + "\n")
    return entry


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--only", default=None,
                    help="comma list: fig09,fig10,fig11,fig12,fig13,"
                         "fig02,dram,kernels,sweep,cache,corpus,"
                         "service,tune,dynamic")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--no-trajectory", action="store_true",
                    help="skip appending the sweep row to BENCH_sweep.json")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a fixed path: the cache directory is part of the entries' key
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_ROOT / ".jax_cache"))

    from benchmarks import (autotune, cache_hierarchy, corpus_sweep,
                            dram_types, dynamic_sweep,
                            fig02_repro_error, fig09_hitgraph,
                            fig10_accugraph, fig11_degree,
                            fig12_comparability, fig13_optimizations,
                            kernel_bench, service_load,
                            sweep_throughput)

    suites = {
        "fig09": lambda: fig09_hitgraph.run(args.scale),
        "fig10": lambda: fig10_accugraph.run(args.scale),
        "fig11": lambda: fig11_degree.run(),
        "fig12": lambda: fig12_comparability.run(args.scale),
        "fig13": lambda: fig13_optimizations.run(args.scale),
        "fig02": lambda: fig02_repro_error.run(args.scale),
        "dram": lambda: dram_types.run(args.scale),
        "kernels": kernel_bench.run,
        "sweep": lambda: sweep_throughput.run(args.scale),
        "cache": lambda: cache_hierarchy.run(args.scale),
        "corpus": lambda: corpus_sweep.run(args.scale),
        "service": lambda: service_load.run(args.scale),
        "tune": lambda: autotune.run(args.scale),
        "dynamic": lambda: dynamic_sweep.run(args.scale),
    }

    all_rows = []
    rows_by_suite = {}
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        rows = fn()
        wall = time.perf_counter() - t0
        all_rows.extend(rows)
        rows_by_suite[name] = rows
        for r in rows:
            if "us_per_call" in r:
                print(f"{r['name']},{r['us_per_call']:.1f},"
                      f"{r.get('derived', '')}")
            else:
                key = "-".join(str(r.get(k)) for k in
                               ("dataset", "problem", "variant",
                                "avg_degree", "dram", "system")
                               if r.get(k) is not None)
                val_us = r.get("wall_s", 0) * 1e6
                derived = ";".join(
                    f"{k}={round(v, 4) if isinstance(v, float) else v}"
                    for k, v in r.items()
                    if k not in ("bench", "wall_s") and v is not None)
                print(f"{r['bench']}:{key},{val_us:.0f},{derived}")
        print(f"# {name}: {len(rows)} rows in {wall:.1f}s",
              file=sys.stderr)
    # the kernels suite emits one sweep-shaped row (variant "kernel",
    # the dram_serve throughput) so the kernel serve path is tracked in
    # the same trajectory file / regression gate as the sweep figures
    traj_rows = list(rows_by_suite.get("sweep", ()))
    traj_rows += [r for r in rows_by_suite.get("kernels", ())
                  if r.get("bench") == "sweep"]
    if traj_rows and not args.no_trajectory:
        entry = append_sweep_trajectory(traj_rows, args.scale)
        print(f"# BENCH_sweep.json += {entry}", file=sys.stderr)
    if "service" in rows_by_suite and not args.no_trajectory:
        entry = append_service_trajectory(rows_by_suite["service"],
                                          args.scale)
        print(f"# BENCH_service.json += {entry}", file=sys.stderr)
    if "tune" in rows_by_suite and not args.no_trajectory:
        entry = append_tune_trajectory(rows_by_suite["tune"],
                                       args.scale)
        print(f"# BENCH_tune.json += {entry}", file=sys.stderr)
    if "dynamic" in rows_by_suite and not args.no_trajectory:
        entry = append_dynamic_trajectory(rows_by_suite["dynamic"],
                                          args.scale)
        print(f"# BENCH_dynamic.json += {entry}", file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(all_rows, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
