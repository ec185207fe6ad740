"""Pallas TPU kernels for the DRAM-timing loop (the paper's hot path).

Two kernels live here:

* :func:`dram_timing_kernel` — the legacy per-channel ``[C, L]`` scan
  (one request per channel per step).  Grid = (channels, trace_chunks):
  channels are independent bank-state machines (the property Ramulator's
  state-machine tree encodes) and map to parallel grid rows; the trace
  dimension is walked sequentially with the bank/rank state resident in
  VMEM scratch — the TPU analogue of the FPGA keeping controller state
  in registers/BRAM.

* :func:`dram_serve_kernel` — the production serve path: the blocked
  ``[S, C, K]`` lockstep stream format that ``VectorizedDRAM.
  run_program`` serves (K row hits or one miss retired per channel per
  step, phase barriers honored in-scan via a branchless carry re-base).
  Channels are coupled at phase boundaries (the re-base shift is the max
  over *all* channels), so the grid walks step *tiles* sequentially and
  the step itself vectorizes over channels.  The step body is
  ``repro.core.vectorized.make_serve_step`` — literally the same traced
  code as the XLA scan backend, so the two ``serve_backend`` paths are
  bit-identical by construction, not merely by test.

BlockSpec tiling streams ``(tile, C, K)`` trace tiles through VMEM
(Pallas double-buffers the next tile's copy-in behind the current tile's
compute); the carry state stays resident in VMEM scratch across the
whole grid, and the boundary flags and timing scalars ride in SMEM.
Every VMEM block is padded to the ``(8, 128)`` int32 tile, so a
``(tile, C, K)`` block costs ``tile * ceil8(C) * 128 * 4`` bytes however
small K is: 4 MiB at ``tile=1024`` for C <= 8, 8 MiB at C=16.  The
three streams (issue, meta in; finish out), double-buffered, therefore
need 24 MiB (C <= 8) to 48 MiB (C=16) of scoped VMEM, above the 16 MiB
default; :func:`_serve_vmem_bytes` computes the bound and the kernel
asks for it explicitly.  Timing parameters ride as a *traced* int32[7]
input (never static), so one compiled kernel serves every speed grade.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import vectorized as vec

NEG_INF32 = -(1 << 30)

#: steps per serve-kernel grid tile.  Both fused-scan chunk-ladder sizes
#: (2**13, 2**17) are multiples, so ladder chunks always tile exactly.
#: 1024 is also the tile of XLA's TPU layout for the int32[S] boundary
#: stream (``T(1024)``); a compiled kernel's 1-D block must match it.
SERVE_TILE = 1024

#: headroom above the blocks' bytes for Mosaic's own scoped scratch
_VMEM_HEADROOM = 2 << 20


def _padded_bytes(shape) -> int:
    """VMEM bytes of an int32 block padded to the ``(8, 128)`` tile."""
    *lead, sub, lane = (1, 1) + tuple(shape)
    return (math.prod(lead) * (-(-sub // 8) * 8)
            * (-(-lane // 128) * 128) * 4)


def _carry_shapes(C: int, B: int, R: int):
    return [(C, B), (C, B), (C,), (C, R, 4), (C, R), (C,)]


def _serve_vmem_bytes(tile: int, C: int, K: int, B: int, R: int) -> int:
    """Scoped-VMEM bound of :func:`dram_serve_kernel`: the three
    ``(tile, C, K)`` streams and the carry blocks in and out, each
    double-buffered, plus the resident carry scratch, all padded to the
    ``(8, 128)`` tile."""
    carry = sum(_padded_bytes(s) for s in _carry_shapes(C, B, R))
    return 2 * (3 * _padded_bytes((tile, C, K)) + 2 * carry) + carry


def _kernel(issue_ref, bank_ref, row_ref, valid_ref, timing_ref,
            finish_ref, kind_ref,
            open_row, act_time, bank_avail, bus_free,
            act_hist, act_ptr, last_act,
            *, chunk: int, n_banks: int, banks_per_rank: int):
    t_idx = pl.program_id(1)
    tCL, tRCD, tRP, tRAS, tBL, tRRD, tFAW = (
        timing_ref[i] for i in range(7))

    @pl.when(t_idx == 0)
    def _init():
        open_row[...] = jnp.full_like(open_row[...], -1)
        act_time[...] = jnp.full_like(act_time[...], NEG_INF32)
        bank_avail[...] = jnp.zeros_like(bank_avail[...])
        bus_free[...] = jnp.zeros_like(bus_free[...])
        act_hist[...] = jnp.full_like(act_hist[...], NEG_INF32)
        act_ptr[...] = jnp.zeros_like(act_ptr[...])
        last_act[...] = jnp.full_like(last_act[...], NEG_INF32)

    def body(j, _):
        b = bank_ref[0, j]
        r = row_ref[0, j]
        iss = issue_ref[0, j]
        v = valid_ref[0, j]
        rank = b // banks_per_rank

        o = open_row[b]
        at = act_time[b]
        av = bank_avail[b]
        bf = bus_free[0]
        ptr = act_ptr[rank]
        la = last_act[rank]
        oldest = act_hist[rank, ptr]

        hit = o == r
        empty = o == -1
        base = jnp.maximum(iss, av)
        act_floor = jnp.maximum(la + tRRD, oldest + tFAW)
        act = jnp.where(
            empty,
            jnp.maximum(base, act_floor),
            jnp.maximum(jnp.maximum(base, at + tRAS) + tRP, act_floor),
        )
        col = jnp.where(hit, base, act + tRCD)
        finish = jnp.maximum(col + tCL, bf) + tBL
        kind = jnp.where(hit, 0, jnp.where(empty, 1, 2)).astype(jnp.int32)
        did_act = jnp.logical_and(jnp.logical_not(hit), v)

        upd = jnp.logical_and(v, True)
        open_row[b] = jnp.where(upd & ~hit, r, o)
        act_time[b] = jnp.where(did_act, act, at)
        bank_avail[b] = jnp.where(upd, col + tBL, av)
        bus_free[0] = jnp.where(upd, finish, bf)
        act_hist[rank, ptr] = jnp.where(did_act, act, oldest)
        act_ptr[rank] = jnp.where(did_act, (ptr + 1) % 4, ptr)
        last_act[rank] = jnp.where(did_act, act, la)

        finish_ref[0, j] = jnp.where(v, finish, 0)
        kind_ref[0, j] = jnp.where(v, kind, -1)
        return 0

    jax.lax.fori_loop(0, chunk, body, 0)


def dram_timing_kernel(
    issue: jnp.ndarray, bank: jnp.ndarray, row: jnp.ndarray,
    valid: jnp.ndarray, timing: jnp.ndarray, *, n_banks: int,
    banks_per_rank: int, chunk: int = 512, interpret: bool = False,
):
    """Run the timing scan over ``[C, L]`` per-channel padded streams.

    ``timing`` is the *traced* int32[7] vector
    (:func:`repro.core.vectorized.timing_params` order) — one compiled
    kernel serves every speed grade; L must be a multiple of ``chunk``.
    Returns (finish, kind) int32[C, L].
    """
    C, L = issue.shape
    assert L % chunk == 0, (L, chunk)
    n_ranks = max(n_banks // banks_per_rank, 1)
    grid = (C, L // chunk)
    spec = pl.BlockSpec((1, chunk), lambda c, t: (c, t))
    tspec = pl.BlockSpec((7,), lambda c, t: (0,))
    kern = functools.partial(
        _kernel, chunk=chunk, n_banks=n_banks,
        banks_per_rank=banks_per_rank,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec, spec, spec, spec, tspec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((C, L), jnp.int32),
            jax.ShapeDtypeStruct((C, L), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_banks,), jnp.int32),      # open_row
            pltpu.VMEM((n_banks,), jnp.int32),      # act_time
            pltpu.VMEM((n_banks,), jnp.int32),      # bank_avail
            pltpu.VMEM((1,), jnp.int32),            # bus_free
            pltpu.VMEM((n_ranks, 4), jnp.int32),    # act_hist
            pltpu.VMEM((n_ranks,), jnp.int32),      # act_ptr
            pltpu.VMEM((n_ranks,), jnp.int32),      # last_act
        ],
        interpret=interpret,
    )(issue.astype(jnp.int32), bank.astype(jnp.int32),
      row.astype(jnp.int32), valid.astype(jnp.int32),
      timing.astype(jnp.int32))


def _serve_kernel(issue_ref, meta_ref, boundary_ref, timing_ref,
                  avail_in, act_in, bus_in, hist_in, ptr_in, pmf_in,
                  fin_ref, avail_out, act_out, bus_out, hist_out,
                  ptr_out, pmf_out,
                  avail_s, act_s, bus_s, hist_s, ptr_s, pmf_s,
                  *, tile: int, banks_per_rank: int):
    t_idx = pl.program_id(0)

    @pl.when(t_idx == 0)
    def _init():
        avail_s[...] = avail_in[...]
        act_s[...] = act_in[...]
        bus_s[...] = bus_in[...]
        hist_s[...] = hist_in[...]
        ptr_s[...] = ptr_in[...]
        pmf_s[...] = pmf_in[...]

    C, B = avail_s.shape
    R = hist_s.shape[1]
    K = issue_ref.shape[2]
    # timing and boundary live in SMEM: scalar reads at a dynamic index
    # are not expressible as vector loads from a 1-D VMEM ref
    timing = [timing_ref[i] for i in range(len(vec.TIMING_FIELDS))]
    step = vec.make_serve_step(timing, C, B, R, K, banks_per_rank)

    def body(j, _):
        state = (avail_s[...], act_s[...], bus_s[...], hist_s[...],
                 ptr_s[...], pmf_s[...])
        x = (issue_ref[j], meta_ref[j], boundary_ref[j] != 0)
        (avail, act, bus, hist, ptr, pmf), fin = step(state, x)
        avail_s[...] = avail
        act_s[...] = act
        bus_s[...] = bus
        hist_s[...] = hist
        ptr_s[...] = ptr
        pmf_s[...] = pmf
        fin_ref[j] = fin
        return 0

    jax.lax.fori_loop(0, tile, body, 0)

    avail_out[...] = avail_s[...]
    act_out[...] = act_s[...]
    bus_out[...] = bus_s[...]
    hist_out[...] = hist_s[...]
    ptr_out[...] = ptr_s[...]
    pmf_out[...] = pmf_s[...]


def dram_serve_kernel(
    issue: jnp.ndarray, meta: jnp.ndarray, boundary: jnp.ndarray,
    timing: jnp.ndarray, avail: jnp.ndarray, act: jnp.ndarray,
    bus: jnp.ndarray, hist: jnp.ndarray, ptr: jnp.ndarray,
    pmf: jnp.ndarray, *, banks_per_rank: int, tile: int = SERVE_TILE,
    interpret: bool = False,
):
    """Serve one fused-scan chunk of blocked ``[S, C, K]`` streams.

    The six carry arrays are the in-scan serve state (persistent lean
    carry + phase-makespan accumulator, see
    ``repro.core.vectorized.init_lean_carry``); ``boundary`` is int32[S]
    (nonzero = phase's last step), ``timing`` the traced int32[7]
    vector.  S must be a multiple of ``tile`` (the ops wrapper pads
    with invalid steps, which are state no-ops).  Returns
    ``(finish[S, C, K], (avail, act, bus, hist, ptr, pmf))`` —
    bit-identical to ``vec._fused_scan_core`` on the same inputs.
    """
    S, C, K = issue.shape
    assert S % tile == 0, (S, tile)
    if not interpret and tile % SERVE_TILE:
        raise ValueError(
            f"a compiled serve kernel needs tile % {SERVE_TILE} == 0 "
            f"(the boundary stream's TPU layout), got {tile}")
    B = avail.shape[1]
    R = hist.shape[1]
    grid = (S // tile,)
    stream = pl.BlockSpec((tile, C, K), lambda t: (t, 0, 0))

    def whole(shape):
        ix = tuple(0 for _ in shape)
        return pl.BlockSpec(shape, lambda t, _ix=ix: _ix)

    carry_shapes = _carry_shapes(C, B, R)
    kern = functools.partial(_serve_kernel, tile=tile,
                             banks_per_rank=banks_per_rank)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[stream, stream,
                  pl.BlockSpec((tile,), lambda t: (t,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [whole(s) for s in carry_shapes],
        out_specs=[stream] + [whole(s) for s in carry_shapes],
        out_shape=[jax.ShapeDtypeStruct((S, C, K), jnp.int32)]
        + [jax.ShapeDtypeStruct(s, jnp.int32) for s in carry_shapes],
        scratch_shapes=[pltpu.VMEM(s, jnp.int32) for s in carry_shapes],
        compiler_params=pltpu.CompilerParams(
            # the carry chains through scratch: tiles run in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_serve_vmem_bytes(tile, C, K, B, R)
            + _VMEM_HEADROOM),
        interpret=interpret,
    )(issue.astype(jnp.int32), meta.astype(jnp.int32),
      boundary.astype(jnp.int32), timing.astype(jnp.int32),
      avail, act, bus, hist, ptr, pmf)
    return out[0], tuple(out[1:])
