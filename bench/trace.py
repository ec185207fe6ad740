"""Reduction of a ``jax.profiler`` trace to the benchmark's numbers.

    python bench/trace.py <trace dir>     # print the trace's planes,
                                          # lines and busiest names

Device activity comes from the TPU planes (``/device:TPU:<n>``), from
their ``XLA Modules`` line (one event per program run; the ``XLA Ops``
line holds every op of every loop step, millions of events for a serve
scan, and is not read): busy time is the union of the program
intervals, and each program is attributed to a layer by the name
patterns of ``bench/layers.json``.  Host activity is every other event
on the host plane; an idle gap of the device is named by the shortest
host event that covers its middle.
"""

from __future__ import annotations

import glob
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def union_length(intervals):
    """Total length covered by ``[(start, end), ...]``."""
    total = 0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo, hi):
    """Uncovered ``(start, end)`` stretches of ``[lo, hi]``."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``ProfileData``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(files[-1])


def events(pd):
    """``{"devices": {plane: [(start, end, program), ...]},
    "host": [(start, end, name), ...]}`` with times in ns."""
    devices = {}
    host = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            d = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    d.extend((e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns,
                             f"{line.name}:{e.name}") for e in line.events
                            if e.duration_ns > 0
                            and not e.name.startswith("$"))
    return {"devices": devices, "host": host}


def module_layer(name: str, layers: dict):
    for layer, patterns in layers.items():
        if any(re.search(p, name) for p in patterns):
            return layer
    return None


def summarize(ev, window, layers: dict) -> dict:
    """Reduce trace events to the harness's numbers over ``window``
    (``(start_ns, end_ns)`` on the trace's clock).  Per-layer device
    seconds are summed over all devices; busy seconds are averaged over
    the devices that ran anything."""
    lo, hi = window
    busy = []
    layer_s = {layer: 0.0 for layer in layers}
    seen = {layer: set() for layer in layers}
    prog_time = {}
    all_busy = []
    for programs in ev["devices"].values():
        runs = clip(programs, lo, hi)
        if not runs:
            continue
        busy.append(union_length([(s, e) for s, e, _ in runs]))
        all_busy.extend((s, e) for s, e, _ in runs)
        for s, e, name in runs:
            layer = module_layer(name, layers)
            if layer is not None:
                layer_s[layer] += (e - s) / 1e9
                seen[layer].add(name)
            short = _short(name)
            prog_time[short] = prog_time.get(short, 0.0) + (e - s) / 1e9
    host = clip(ev["host"], lo, hi)
    idle = []
    for s, e in sorted(gaps(all_busy, lo, hi), key=lambda g: g[0] - g[1])[
            :TOP]:
        mid = (s + e) / 2
        cover = [(he - hs, n) for hs, he, n in host if hs <= mid <= he]
        idle.append([min(cover)[1] if cover else "(no host span)",
                     (e - s) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9 if busy else 0.0,
        "devices": len(busy),
        "layer_s": layer_s,
        "layer_programs": {k: sorted(v) for k, v in seen.items()},
        "device_ops": sorted(([k, v] for k, v in prog_time.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": idle,
    }


def _short(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


def host_span(ev, name: str):
    """``(start_ns, end_ns)`` of the host event ``name`` (the harness's
    own ``TraceAnnotation``)."""
    hits = [(s, e) for s, e, n in ev["host"] if n.split(":", 1)[-1] == name]
    if not hits:
        raise LookupError(f"no host span {name!r} in the trace")
    return min(s for s, _ in hits), max(e for _, e in hits)


def describe(pd) -> None:
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            tot = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:8]
            span = ((min(e.start_ns for e in evs),
                     max(e.start_ns + e.duration_ns for e in evs))
                    if evs else None)
            print(f"  LINE {line.name!r} events={len(evs)} span={span}")
            for name, ns in top:
                print(f"    {ns / 1e9:12.6f} s  {name[:120]}")


if __name__ == "__main__":
    describe(load(sys.argv[1]))
