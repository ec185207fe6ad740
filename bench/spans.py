"""The program's host spans in a ``jax.profiler`` trace, and the device's
idle time put down to them.

    python bench/spans.py <trace dir>     # each span's count, total and
                                          # self seconds, and the
                                          # device's idle seconds by span

The program records one span per layer boundary (``repro.obs``: names
with the prefix ``repro.``) as ``TraceAnnotation`` events, which land on
the host plane of the trace, one line per host thread, on the clock of
the device's ``XLA Modules`` line (``bench/trace.py``).

- ``spans``: ``{name: {"count", "total_s", "self_s"}}`` over the window.
  Self time is computed per host line: a span's duration less the part
  of it that its child spans on that line cover (spans of one thread
  nest), summed over lines.
- ``idle_by_span``: ``{name: seconds}``.  Every idle nanosecond of the
  device in the window goes to the shortest span that covers it, on any
  host line, or to ``"(none)"``; an idle gap is cut at span boundaries.
"""

from __future__ import annotations

import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace as trace_mod  # noqa: E402

PREFIX = "repro."
NONE = "(none)"


def span_events(pd):
    """``[(start_ns, end_ns, name, line), ...]``: the program's spans on
    the host plane; ``line`` is the index of the host thread's line
    (threads' line names need not differ)."""
    out = []
    for plane in pd.planes:
        if plane.name != trace_mod.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name, i)
                       for e in line.events
                       if e.duration_ns > 0 and e.name.startswith(PREFIX))
    return out


def events(pd):
    """``bench/trace.events`` with the program's spans under
    ``"spans"``."""
    ev = trace_mod.events(pd)
    ev["spans"] = span_events(pd)
    return ev


def span_times(spans, window):
    """``{name: {"count", "total_s", "self_s"}}`` of the spans clipped to
    ``window``."""
    lo, hi = window
    lines = defaultdict(list)
    for s, e, name, line in spans:
        if e > lo and s < hi:
            lines[line].append((max(s, lo), min(e, hi), name))
    out = {}
    for items in lines.values():
        # a parent sorts before the children it encloses
        items.sort(key=lambda x: (x[0], -x[1]))
        covered = [0] * len(items)
        stack = []
        for i, (s, e, _) in enumerate(items):
            while stack and items[stack[-1]][1] <= s:
                stack.pop()
            if stack:
                parent = stack[-1]
                covered[parent] += min(e, items[parent][1]) - s
            stack.append(i)
        for (s, e, name), c in zip(items, covered):
            t = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
            t["count"] += 1
            t["total_s"] += (e - s) / 1e9
            t["self_s"] += (e - s - c) / 1e9
    return out


def idle_by_span(idle, spans):
    """``{name: seconds}``: each stretch of the ``idle`` intervals given
    to the shortest span over it (ties to the earlier span), or to
    ``"(none)"``."""
    starts = defaultdict(list)
    ends = defaultdict(list)
    for i, (s, e, _, _) in enumerate(spans):
        starts[s].append(i)
        ends[e].append(i)
    idle = sorted(idle)
    cuts = sorted(set(starts) | set(ends) | {p for g in idle for p in g})
    active = set()
    out = {}
    g = 0
    for a, b in zip(cuts, cuts[1:]):
        active.difference_update(ends.get(a, ()))
        active.update(starts.get(a, ()))
        while g < len(idle) and idle[g][1] <= a:
            g += 1
        if g == len(idle):
            break
        if idle[g][0] > a:
            continue
        name = NONE
        if active:
            i = min(active, key=lambda j: (spans[j][1] - spans[j][0], j))
            name = spans[i][2]
        out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def summarize(ev, window):
    """``{"spans", "idle_by_span"}`` of ``ev`` (from :func:`events`) over
    ``window`` (``(start_ns, end_ns)``); the device's idle time is the
    part of the window that no program of any device covers, as in
    ``bench/trace.summarize``."""
    lo, hi = window
    busy = [(s, e) for programs in ev["devices"].values()
            for s, e, _ in trace_mod.clip(programs, lo, hi)]
    return {"spans": span_times(ev["spans"], window),
            "idle_by_span": idle_by_span(trace_mod.gaps(busy, lo, hi),
                                         ev["spans"])}


def window_of(ev):
    """The harness's ``bench.window`` span where the trace has one, else
    the first span's start to the last span's end."""
    try:
        return trace_mod.host_span(ev, "bench.window")
    except LookupError:
        if not ev["spans"]:
            raise LookupError(f"no {PREFIX}* span in the trace") from None
        return (min(s for s, _, _, _ in ev["spans"]),
                max(e for _, e, _, _ in ev["spans"]))


def report(trace_dir) -> None:
    ev = events(trace_mod.load(trace_dir))
    lo, hi = window_of(ev)
    s = summarize(ev, (lo, hi))
    idle = sum(s["idle_by_span"].values())
    print(f"window {(hi - lo) / 1e9:.6f} s, device idle {idle:.6f} s, "
          f"{len(ev['devices'])} device plane(s)")
    print(f"{'span':<22}{'count':>7}{'total_s':>14}{'self_s':>14}"
          f"{'idle_s':>14}")
    names = sorted(set(s["spans"]) | set(s["idle_by_span"]),
                   key=lambda n: -s["idle_by_span"].get(n, 0.0))
    for n in names:
        t = s["spans"].get(n, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        print(f"{n:<22}{t['count']:>7}{t['total_s']:>14.6f}"
              f"{t['self_s']:>14.6f}{s['idle_by_span'].get(n, 0.0):>14.6f}")


if __name__ == "__main__":
    report(sys.argv[1])
