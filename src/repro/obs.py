"""Spans and counters of the simulator's layers.

Spans are ``jax.profiler.TraceAnnotation`` events: under
``jax.profiler.trace(dir)`` they land in the profile's host plane, on the
same clock as the device's programs, so a reduction can put each idle
second of the device down to the host layer that was running.  With no
profile being taken a span costs a few hundred nanoseconds and records
nothing; the profiler, not this module, holds the events.

Span names carry the ``repro.`` prefix and are the constants below, so a
reduction finds them by name after a refactor.  No span encloses a
jitted function's body or adds a device synchronisation: a span around
an asynchronous dispatch measures the host's enqueue and the runtime's
preparation, and host reads of device results sit in
:data:`DEVICE_WAIT` children, so each layer's self time leaves the wait
for the chip out.

Counters are process-wide integers behind one lock (the sweep engine
serves batch groups from worker threads): the serve and pack dispatch
counts (``core.vectorized.dispatch_counts``) and ``serve_lane_slots``,
the ``steps x channels x lanes`` (times the batch) that the serve
dispatches ran, whose ratio with the requests served is the serve
layer's useful-lane share.
"""

from __future__ import annotations

import threading
from typing import Dict

import jax

#: one ``Sweeper.run`` call
SWEEP_RUN = "repro.sweep.run"
#: the serving thread waiting for cases the preparation workers build
WAIT_PREPARE = "repro.wait_prepare"
#: a graph algorithm run (a session cache miss)
ALGORITHM = "repro.algorithm"
#: model construction and the model's request-program build
TRACE_BUILD = "repro.trace_build"
#: the on-chip cache / prefetcher filter over a request program
CACHE_FILTER = "repro.cache_filter"
#: packing a request program into the serve's lockstep streams
PACK = "repro.pack"
#: the serve's chunk dispatches
SERVE = "repro.serve"
#: reducing the serve's finish cycles to phase statistics
REDUCE = "repro.reduce"
#: a host read of a device result already dispatched
DEVICE_WAIT = "repro.device_wait"

SPANS = (SWEEP_RUN, WAIT_PREPARE, ALGORITHM, TRACE_BUILD, CACHE_FILTER,
         PACK, SERVE, REDUCE, DEVICE_WAIT)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A context manager recording ``name`` as a host span of the
    profile being taken, if any."""
    return jax.profiler.TraceAnnotation(name)


_COUNTS = {"packed": 0, "fused": 0, "fused_batch": 0, "device_pack": 0,
           "pallas": 0, "serve_lane_slots": 0}
_LOCK = threading.Lock()


def count(kind: str, n: int = 1) -> None:
    """Add ``n`` to counter ``kind`` (a ``KeyError`` for an unknown
    one)."""
    with _LOCK:
        _COUNTS[kind] += n


def counts() -> Dict[str, int]:
    """A snapshot of every counter."""
    with _LOCK:
        return dict(_COUNTS)


def reset() -> None:
    """Set every counter to zero."""
    with _LOCK:
        for k in _COUNTS:
            _COUNTS[k] = 0
