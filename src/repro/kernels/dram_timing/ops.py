"""Jitted public wrappers for the dram_timing Pallas kernels.

The layering contract (see ``src/repro/kernels/README.md``): kernel.py
holds the raw ``pallas_call`` builders (explicit ``interpret`` bool),
ref.py the pure-jnp oracles, and this module the public ops — jitted,
with interpret mode taken from the platform alone: compiled on the TPU,
interpreted on the CPU, where compiling a TPU kernel is impossible.
There is no knob, so interpret mode is never reachable on the chip.

Timing parameters are **traced** int32[7] inputs, never static jit
arguments: one compiled kernel serves every DDR3/DDR4/HBM speed grade.
The only static argnames left are true shape/codegen parameters
(``chunk``/``tile`` block sizes, bank geometry, ``interpret``), and the
block sizes come from a fixed ladder — the jit cache stays at the two
fixed chunk shapes per geometry instead of recompiling per value.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dram import DRAMConfig
from repro.core.trace import Trace
from repro.core.vectorized import pack_channels
from repro.kernels.dram_timing.kernel import (SERVE_TILE,
                                              dram_serve_kernel,
                                              dram_timing_kernel)


def _interpret() -> bool:
    """Interpret mode on the CPU, the only way to run the kernel body
    there; compiled everywhere else."""
    return jax.default_backend() == "cpu"


@functools.partial(
    jax.jit,
    static_argnames=("n_banks", "banks_per_rank", "chunk", "interpret"))
def _dram_timing(issue, bank, row, valid, timing, *, n_banks,
                 banks_per_rank, chunk, interpret):
    return dram_timing_kernel(
        issue, bank, row, valid, timing, n_banks=n_banks,
        banks_per_rank=banks_per_rank, chunk=chunk, interpret=interpret,
    )


def dram_timing(issue, bank, row, valid, timing, *, n_banks,
                banks_per_rank, chunk=512):
    """Per-channel ``[C, L]`` timing scan (one request per channel per
    step).  ``timing`` is the traced int32[7] vector; returns
    ``(finish, kind)`` int32[C, L]."""
    return _dram_timing(
        issue, bank, row, valid, jnp.asarray(timing, dtype=jnp.int32),
        n_banks=n_banks, banks_per_rank=banks_per_rank, chunk=chunk,
        interpret=_interpret())


@functools.partial(
    jax.jit, static_argnames=("banks_per_rank", "tile", "interpret"))
def _dram_serve(issue, meta, boundary, timing, avail, act, bus, hist,
                ptr, pmf, *, banks_per_rank, tile, interpret):
    return dram_serve_kernel(
        issue, meta, boundary, timing, avail, act, bus, hist, ptr, pmf,
        banks_per_rank=banks_per_rank, tile=tile, interpret=interpret,
    )


def dram_serve(issue, meta, boundary, timing, state, *, banks_per_rank,
               tile=SERVE_TILE):
    """Serve one fused-scan chunk of blocked ``[S, C, K]`` lockstep
    streams through the Pallas serve kernel.

    Drop-in for one ``vec._fused_scan`` chunk dispatch: ``state`` is the
    in-scan 6-tuple carry, ``boundary`` bool/int[S].  S is padded up to
    a multiple of ``tile`` with invalid steps (state no-ops: every
    update is a max against identities and the re-base shift is 0), so
    any chunk-ladder size — or an arbitrary test shape — works.
    Returns ``(finish[S, C, K], state)``, bit-identical to the scan.
    """
    S = issue.shape[0]
    pad = (-S) % tile
    issue = jnp.asarray(issue, dtype=jnp.int32)
    meta = jnp.asarray(meta, dtype=jnp.int32)
    boundary = jnp.asarray(boundary).astype(jnp.int32)
    if pad:
        issue = jnp.pad(issue, ((0, pad), (0, 0), (0, 0)))
        meta = jnp.pad(meta, ((0, pad), (0, 0), (0, 0)))
        boundary = jnp.pad(boundary, ((0, pad),))
    fin, state = _dram_serve(
        issue, meta, boundary, jnp.asarray(timing, dtype=jnp.int32),
        *state, banks_per_rank=banks_per_rank, tile=tile,
        interpret=_interpret())
    return fin[:S], state


def simulate_trace_kernel(trace: Trace, cfg: DRAMConfig,
                          chunk: int = 512):
    """End-to-end: Trace -> per-channel pack -> kernel -> makespan."""
    packed = pack_channels(trace, cfg)
    C, L = packed.issue.shape
    Lp = int(np.ceil(L / chunk)) * chunk
    pad = Lp - L

    def _pad(a, fill=0):
        return np.pad(a, ((0, 0), (0, pad)), constant_values=fill)

    t = cfg.timing
    timing = np.array([t.tCL, t.tRCD, t.tRP, t.tRAS, t.tBL, t.tRRD,
                       t.tFAW], dtype=np.int32)
    finish, kind = dram_timing(
        jnp.asarray(_pad(packed.issue)), jnp.asarray(_pad(packed.bank)),
        jnp.asarray(_pad(packed.row)), jnp.asarray(_pad(packed.valid)),
        timing, n_banks=cfg.banks_per_channel,
        banks_per_rank=cfg.org.banks, chunk=chunk,
    )
    finish = np.asarray(finish)[:, :L]
    kind = np.asarray(kind)[:, :L]
    valid = packed.valid
    makespan = int(finish[valid].max()) if valid.any() else 0
    return finish, kind, makespan
