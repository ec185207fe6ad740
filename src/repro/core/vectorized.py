"""Vectorized DRAM-timing model: ``lax.scan`` over per-channel streams.

Implements exactly the semantics of :mod:`repro.core.timing` (bit-exact on
integer cycles; property-tested) but as a JAX program:

* channels are independent -> packed to a ``[C, L]`` batch and ``vmap``-ed,
* each channel is an associative-state scan with carry
  ``(open_row[B], act_time[B], bank_avail[B], bus_free)``.

This is the TPU-native adaptation of the paper's hot loop: Ramulator ticks
one cycle at a time; we exploit the same structural property Ramulator's
state-machine tree encodes (banks evolve independently except for the
shared data bus, which is a running max) to turn the event loop into a
scan.  The Pallas kernel (``kernels/dram_timing``) fuses the same scan with
VMEM-resident state; this module is its jnp oracle *and* the fast path on
CPU.

Two entry points:

* :func:`simulate_packed` — one phase, channels ``vmap``-ed over a
  ``[C, L]`` batch (the legacy per-phase path);
* :func:`fused_scan` — a whole multi-phase program in one scan: channels
  step in lockstep over blocked ``[S, C, K]`` streams (a step retires up
  to K row hits per channel, or one miss) and phase barriers are honored
  *inside* the scan (the carry is re-based by the global makespan at
  each segment boundary), so an entire simulation run costs a handful of
  fixed-shape chunk dispatches instead of two dispatches per iteration.

DRAM timing parameters (``tCL``, ``tRCD``, ...) are *traced* int32 inputs,
not static jit arguments: one compiled scan serves DDR3 / DDR4 / HBM2 /
HBM2E, and the fused scan can be ``vmap``-ed over a batch of memory
configurations (see ``repro.sim.sweep(batch_memories=True)``).

Cycle math is int32 (TPU-friendly): each *phase* must satisfy
``max_cycles < 2**31`` (asserted); the fused scan re-bases at every
barrier, so whole runs of arbitrary length are fine.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.dram import DRAMConfig, DRAMTiming, CACHE_LINE_BYTES
from repro.core import timing as timing_mod
from repro.core.trace import Trace, group_ranks

NEG_INF32 = -(1 << 30)

#: per-phase relative issue cycles must stay below this (int32 headroom)
MAX_PHASE_ISSUE = 2**31 - 2**26

TIMING_FIELDS = ("tCL", "tRCD", "tRP", "tRAS", "tBL", "tRRD", "tFAW")

#: lanes per block in the fused scan (requests per channel per step);
#: hit-heavy programs use wide blocks, conflict-heavy ones serialize.
#: 8 is the measured sweet spot: the step's in-block chain resolution is
#: O(K^2), so wider blocks (16/32 were tried) shorten the scan less than
#: they fatten the step on these run-length distributions.
BLOCK_LANES = 8


def choose_block_lanes(n_miss: int, n: int) -> int:
    """Shared host/device block-width rule (exact integer threshold):
    hit-dominated streams (<1/2 misses) get 8 lanes, conflict-heavy ones
    serialize (almost every block would be a singleton miss anyway)."""
    return BLOCK_LANES if 2 * n_miss < n else 1

#: jitted-scan dispatch counters (see :func:`dispatch_counts`; kept in
#: :mod:`repro.obs` beside the serve's lane counter); the throughput
#: benchmark asserts a run costs a few fused chunk dispatches, never the
#: legacy two per iteration.  ``device_pack`` counts whole
#: device-resident pack invocations (each is two jitted dispatches:
#: classify+blocks, then the scatter).  ``pallas`` counts fused-serve
#: chunks dispatched through the Pallas kernel instead of the XLA scan.
DISPATCH_KINDS = ("packed", "fused", "fused_batch", "device_pack",
                  "pallas")


def count_dispatch(kind: str, n: int = 1) -> None:
    """Thread-safe counter bump (the sweep engine serves independent
    batch groups from worker threads)."""
    obs.count(kind, n)


def dispatch_counts() -> Dict[str, int]:
    c = obs.counts()
    return {k: c[k] for k in DISPATCH_KINDS}


def reset_dispatch_counts() -> None:
    """Zero every counter of :mod:`repro.obs`, the lane counter too."""
    obs.reset()


#: legal values of the ``serve_backend`` knob (``DRAMConfig`` field /
#: ``simulate(serve_backend=...)``).  ``scan`` is the XLA ``lax.scan``
#: serve path; ``pallas`` the VMEM-resident kernel in
#: ``repro.kernels.dram_timing`` (bit-identical by construction: both
#: run :func:`make_serve_step`).
SERVE_BACKENDS = ("auto", "scan", "pallas")


def resolve_serve_backend(backend: str = "auto") -> str:
    """Resolve the ``serve_backend`` knob to ``scan`` or ``pallas``.

    ``auto`` is the Pallas kernel on the TPU, the platform it is written
    for and compiles on at every preset geometry, and the XLA scan
    everywhere else.  On the CPU the kernel could only run in interpret
    mode (an eval loop, orders of magnitude slower — fine for parity
    tests, wrong for serving).  An explicit ``pallas`` on a platform
    that is neither raises.  ``REPRO_SERVE_BACKEND`` overrides ``auto``
    only; an explicit argument always wins.
    """
    platform = jax.default_backend()
    if backend == "auto":
        env = os.environ.get("REPRO_SERVE_BACKEND", "")
        if env not in ("scan", "pallas"):
            return "pallas" if platform == "tpu" else "scan"
        backend = env
    if backend not in ("scan", "pallas"):
        raise ValueError(
            f"serve_backend must be one of {SERVE_BACKENDS}, got "
            f"{backend!r}")
    if backend == "pallas" and platform not in ("tpu", "cpu"):
        raise ValueError(
            f"serve_backend='pallas' is a TPU kernel (interpret mode on "
            f"the CPU) and cannot run on {platform!r}")
    return backend


def timing_params(t: DRAMTiming) -> np.ndarray:
    """Timing parameters as the traced int32[7] the scans consume."""
    return np.array([getattr(t, f) for f in TIMING_FIELDS], dtype=np.int32)


@dataclasses.dataclass(frozen=True)
class PackedChannels:
    """Per-channel padded request streams + scatter metadata."""

    issue: np.ndarray        # int32[C, L]
    bank: np.ndarray         # int32[C, L]
    row: np.ndarray          # int32[C, L]
    valid: np.ndarray        # bool[C, L]
    scatter_index: np.ndarray  # int64[C, L] -> position in original trace


def pack_streams(ch: np.ndarray, issue: np.ndarray, bank: np.ndarray,
                 row: np.ndarray, channels: int, length: int):
    """Scatter program-order request components into padded per-channel
    streams (single stable argsort — the shared packing helper behind
    :func:`pack_channels` and the phase/fused backends in
    :mod:`repro.core.accel`).

    Returns ``(issue[C, L] int32, bank[C, L] int32, row[C, L] int32,
    valid[C, L] bool, slot[n] int64)`` where ``slot`` is each request's
    position within its channel stream.
    """
    counts = np.bincount(ch, minlength=channels)
    slot = group_ranks(counts, ch)
    issue_p = np.zeros((channels, length), dtype=np.int32)
    bank_p = np.zeros((channels, length), dtype=np.int32)
    row_p = np.zeros((channels, length), dtype=np.int32)
    valid_p = np.zeros((channels, length), dtype=bool)
    issue_p[ch, slot] = issue
    bank_p[ch, slot] = bank
    row_p[ch, slot] = row
    valid_p[ch, slot] = True
    return issue_p, bank_p, row_p, valid_p, slot


def pack_channels(trace: Trace, cfg: DRAMConfig) -> PackedChannels:
    """Split a program-order trace into per-channel padded streams."""
    comps = cfg.decode_lines(trace.line_addr)
    ch = comps["channel"]
    C = cfg.channels
    counts = np.bincount(ch, minlength=C)
    L = max(int(counts.max()) if len(trace) else 0, 1)
    if np.any(trace.issue < 0) or np.any(trace.issue >= MAX_PHASE_ISSUE):
        raise ValueError("issue cycles out of int32 range; chunk the trace")
    issue, bank, row, valid, slot = pack_streams(
        ch, trace.issue, comps["bank_in_channel"], comps["row"], C, L)
    scatter = np.zeros((C, L), dtype=np.int64)
    scatter[ch, slot] = np.arange(len(trace), dtype=np.int64)
    return PackedChannels(issue, bank, row, valid, scatter)


def init_channel_carry(n_banks: int, banks_per_rank: int):
    """Initial scan carry for one channel (exposed for phase chaining)."""
    n_ranks = n_banks // banks_per_rank
    return (
        jnp.full((n_banks,), -1, dtype=jnp.int32),         # open_row
        jnp.full((n_banks,), NEG_INF32, dtype=jnp.int32),  # act_time
        jnp.zeros((n_banks,), dtype=jnp.int32),            # bank_avail
        jnp.zeros((), dtype=jnp.int32),                    # bus_free
        jnp.full((n_ranks, 4), NEG_INF32, dtype=jnp.int32),  # act_hist
        jnp.zeros((n_ranks,), dtype=jnp.int32),            # act_ptr
        jnp.full((n_ranks,), NEG_INF32, dtype=jnp.int32),  # last_act_rank
    )


def rebase_carry(carry, shift):
    """Shift all time-like carry components ``shift`` cycles into the past,
    clamped at ``NEG_INF32`` (overflow-safe: computed as
    ``max(t, shift + NEG_INF32) - shift``).

    The service recurrence is shift-equivariant (every operation is a max
    or an add of a constant), and clamping only touches values that are
    already below any reachable future time, so a re-based scan is
    bit-equivalent to an absolute-time one — this is what lets the fused
    scan cross phase barriers without returning to Python and lets whole
    runs exceed the int32 cycle range.
    """
    (open_row, act_time, bank_avail, bus_free,
     act_hist, act_ptr, last_act_rank) = carry

    def sh(x):
        return jnp.maximum(x, shift + NEG_INF32) - shift

    return (open_row, sh(act_time), sh(bank_avail), sh(bus_free),
            sh(act_hist), act_ptr, sh(last_act_rank))


def _request_step(state, x, t):
    """Serve one request on one channel: the shared scan step.

    ``t`` is the 7-tuple of (traced) timing scalars in
    :data:`TIMING_FIELDS` order.  Invalid lanes (``v == False``) leave the
    state untouched and emit ``(0, -1)``.
    """
    tCL, tRCD, tRP, tRAS, tBL, tRRD, tFAW = t
    (open_row, act_time, bank_avail, bus_free,
     act_hist, act_ptr, last_act_rank) = state
    iss, b, r, v = x
    banks_per_rank = open_row.shape[0] // act_ptr.shape[0]
    rank = b // banks_per_rank
    o = open_row[b]
    av = bank_avail[b]
    at = act_time[b]
    hit = o == r
    empty = o == -1
    base = jnp.maximum(iss, av)
    # ACT rate limits per rank (tRRD, tFAW over the 4th-last ACT)
    ptr = act_ptr[rank]
    act_floor = jnp.maximum(last_act_rank[rank] + tRRD,
                            act_hist[rank, ptr] + tFAW)
    act = jnp.where(
        empty,
        jnp.maximum(base, act_floor),
        jnp.maximum(jnp.maximum(base, at + tRAS) + tRP, act_floor),
    )
    col = jnp.where(hit, base, act + tRCD)
    finish = jnp.maximum(col + tCL, bus_free) + tBL
    kind = jnp.where(hit, 0, jnp.where(empty, 1, 2)).astype(jnp.int8)
    did_act = jnp.logical_not(hit)
    new_state = (
        open_row.at[b].set(jnp.where(hit, o, r)),
        act_time.at[b].set(jnp.where(hit, at, act)),
        bank_avail.at[b].set(col + tBL),
        finish,
        act_hist.at[rank, ptr].set(
            jnp.where(did_act, act, act_hist[rank, ptr])),
        act_ptr.at[rank].set(
            jnp.where(did_act, (ptr + 1) % 4, ptr)),
        last_act_rank.at[rank].set(
            jnp.where(did_act, act, last_act_rank[rank])),
    )
    state = jax.tree.map(
        lambda new, old: jnp.where(v, new, old), new_state, state
    )
    out = (jnp.where(v, finish, jnp.int32(0)),
           jnp.where(v, kind, jnp.int8(-1)))
    return state, out


def _channel_scan(issue, bank, row, valid, t, carry):
    """Scan one channel's stream. Returns (finish[L], kind[L], carry)."""

    def step(state, x):
        return _request_step(state, x, t)

    carry, (finish, kind) = jax.lax.scan(
        step, carry, (issue, bank, row, valid)
    )
    return finish, kind, carry


@functools.partial(jax.jit, static_argnames=("n_banks", "banks_per_rank"))
def _simulate_packed(issue, bank, row, valid, timing, n_banks,
                     banks_per_rank, carry=None):
    t = tuple(timing[i] for i in range(len(TIMING_FIELDS)))
    if carry is None:
        single = init_channel_carry(n_banks, banks_per_rank)
        carry = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (issue.shape[0],) + x.shape),
            single)
    finish, kind, carry = jax.vmap(
        lambda i, b, r, v, c: _channel_scan(i, b, r, v, t, c))(
            issue, bank, row, valid, carry)
    return finish, kind, carry


def simulate_packed(issue, bank, row, valid, timing, n_banks,
                    banks_per_rank, carry=None):
    """Dispatch-counted wrapper around the jitted per-phase scan."""
    count_dispatch("packed")
    return _simulate_packed(
        jnp.asarray(issue), jnp.asarray(bank), jnp.asarray(row),
        jnp.asarray(valid), jnp.asarray(timing, dtype=jnp.int32),
        n_banks, banks_per_rank, carry)


# ---------------------------------------------------------------------------
# Fused whole-program scan: all phases of a run in one dispatch.
#
# The scan state deliberately avoids gathers/scatters (XLA CPU executes
# them ~10x slower than dense ops inside a scan): per-bank state is
# addressed with one-hot masks over the tiny [C, B] arrays, and the
# row-buffer *classification* (hit / empty / conflict) is precomputed on
# the host — it depends only on each bank's row sequence, never on timing
# — so the device scan only chains the max-plus timing recurrences.
# ---------------------------------------------------------------------------

def init_lean_carry(channels: int, n_banks: int, banks_per_rank: int):
    """Initial fused-scan carry: ``(avail[C,B], act[C,B], bus[C],
    act_hist[C,R,4], act_ptr[C,R])``.  ``last_act`` is not carried — it is
    always ``act_hist[ptr - 1]`` (the most recent push)."""
    n_ranks = n_banks // banks_per_rank
    C = channels
    return (
        jnp.zeros((C, n_banks), dtype=jnp.int32),             # bank_avail
        jnp.full((C, n_banks), NEG_INF32, dtype=jnp.int32),   # act_time
        jnp.zeros((C,), dtype=jnp.int32),                     # bus_free
        jnp.full((C, n_ranks, 4), NEG_INF32, dtype=jnp.int32),  # act_hist
        jnp.zeros((C, n_ranks), dtype=jnp.int32),             # act_ptr
    )


def lean_from_full(carry):
    """Convert a per-channel ``init_channel_carry`` pytree (leading C
    axis) to the fused-scan carry (drops ``open_row`` — host-tracked —
    and ``last_act_rank`` — derivable from the history)."""
    (open_row, act_time, bank_avail, bus_free,
     act_hist, act_ptr, last_act_rank) = carry
    return (bank_avail, act_time, bus_free, act_hist,
            act_ptr.astype(jnp.int32))


def full_from_lean(lean, open_row):
    """Inverse of :func:`lean_from_full`; ``open_row`` is the host-tracked
    int32[C, B] row state."""
    avail, act, bus, hist, ptr = lean
    last = jnp.take_along_axis(hist, ((ptr + 3) % 4)[..., None],
                               axis=2)[..., 0]
    return (jnp.asarray(open_row, dtype=jnp.int32), act, avail, bus,
            hist, ptr, last)


def _lean_rebase(avail, act, bus, hist, shift):
    def sh(x):
        return jnp.maximum(x, shift + NEG_INF32) - shift
    return sh(avail), sh(act), sh(bus), sh(hist)


#: bit layout of the packed per-request metadata word (``meta`` stream):
#: bits 0..7 bank-in-channel, 8 miss, 9 conflict, 10 valid,
#: 11..15 bank-rank within the block (for the in-step hit chain;
#: 5 bits covers BLOCK_LANES_WIDE - 1).
META_MISS, META_CONFL, META_VALID = 1 << 8, 1 << 9, 1 << 10
META_RB_SHIFT = 11
META_RB_MASK = 0x1F


def pack_meta(bank: np.ndarray, miss: np.ndarray, confl: np.ndarray,
              valid: np.ndarray, bank_rank=None) -> np.ndarray:
    """Fuse the per-request metadata into one int32 stream (one scan-step
    slice instead of four)."""
    meta = np.asarray(bank, dtype=np.int32).copy()
    meta |= np.asarray(miss, dtype=np.int32) << 8
    meta |= np.asarray(confl, dtype=np.int32) << 9
    meta |= np.asarray(valid, dtype=np.int32) << 10
    if bank_rank is not None:
        meta |= np.asarray(bank_rank, dtype=np.int32) << META_RB_SHIFT
    return meta


# ---------------------------------------------------------------------------
# Device-resident program packing: the whole pack path (address decode,
# row-kind classification, block decomposition, lockstep scatter) as two
# fixed-shape jitted dispatches, bit-identical to the NumPy packer in
# ``repro.core.accel.pack_program`` (the reference implementation).
#
# The classify/block program moves request-length arrays only through
# its two sorts, which carry their payloads as extra operands: the bank
# sort carries the row, program index, (phase, channel) key and issue
# cycle; the grouped sort takes its keys from the bank sort's output.
# Everything else is elementwise, a prefix sum or prefix max, or segment
# arithmetic over contiguous ranges: a phase is a prefix sum of markers
# at the phase offsets, runs and blocks are numbered by prefix sums,
# and per-bank, per-group and per-phase values are read or written at
# segment bounds found by a searchsorted of the few segment keys into
# the sorted keys.  No scatter, gather or binary search runs at the
# request length.
#
# Shapes are bucketed: requests pad to the next power of two, phases to
# the next power of two, steps to the fused-scan chunk ladder — so the
# jit cache stays logarithmic in program size.  All transfers are int32
# (line addresses and issue cycles are range-checked on the host first),
# halving the host->device bytes of the int64 trace arrays; everything
# downstream of the transfer stays on the device.
# ---------------------------------------------------------------------------

def _decode_device(line, spec, banks):
    """Shift/mask decode of int32 line addresses on device (pow2 sizes
    only; mirrors ``DRAMConfig.decode_lines``)."""
    comps = {}
    for comp, shift, mask in spec:
        comps[comp] = (line >> shift) & mask
    comps["bank_in_channel"] = comps["rank"] * banks + comps["bank"]
    return comps


def _shift_in(x, fill):
    """``x`` moved one place towards the end, ``fill`` entering first."""
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def _segments(sorted_keys, n_keys):
    """``(start, end)``, int32[n_keys]: the range each key value
    ``0..n_keys-1`` holds in the non-negative ``sorted_keys`` (``start ==
    end`` where it is absent)."""
    end = jnp.searchsorted(
        sorted_keys, jnp.arange(n_keys, dtype=sorted_keys.dtype),
        side="right").astype(jnp.int32)
    return _shift_in(end, 0), end


def _classify_bank_order(comps, n, open_row, payloads):
    """Sort a padded program by global bank (program order within each
    bank; padding last) and classify each request's row against the row
    its bank had open before it (mirrors ``classify_rows``).

    ``comps`` is the program's decode (:func:`_decode_device`),
    ``payloads`` int32[Npad] program-order arrays the sort carries.
    Returns ``(kind_o, bank_o, open_out, idx_o, payloads_o)`` in bank
    order: int32 kinds (0 hit / 1 empty / 2 conflict; 0 on padding), the
    global bank (``C * B`` on padding), the int32[C, B] row state after
    the program, and the program index of each position."""
    C, B = open_row.shape
    Npad = comps["row"].shape[0]
    idx = jnp.arange(Npad, dtype=jnp.int32)
    bank = jnp.where(idx < n, comps["channel"] * B
                     + comps["bank_in_channel"], C * B)
    bank_o, idx_o, rows_o, *payloads_o = jax.lax.sort(
        (bank, idx, comps["row"], *payloads), num_keys=2)
    start, end = _segments(bank_o, C * B)
    used = end > start
    open_flat = open_row.reshape(-1)
    # each bank's entering row, set where its segment starts
    entering = jnp.zeros(Npad, jnp.int32).at[
        jnp.where(used, start, Npad)].set(open_flat, mode="drop")
    first = bank_o != _shift_in(bank_o, -1)
    prev = jnp.where(first, entering, _shift_in(rows_o, 0))
    kind_o = jnp.where(prev == rows_o, 0, jnp.where(prev == -1, 1, 2))
    kind_o = jnp.where(bank_o < C * B, kind_o, 0)
    open_out = jnp.where(used, rows_o[jnp.maximum(end - 1, 0)],
                         open_flat).reshape(C, B)
    return kind_o, bank_o, open_out, idx_o, payloads_o


@functools.partial(jax.jit,
                   static_argnames=("spec", "C", "B", "banks"))
def _device_pack_core(line, issue, offsets, n, open_row, spec, C, B,
                      banks):
    """Classify + block-decompose a padded program on device.

    ``line``/``issue`` are int32[Npad] (padded past ``n``), ``offsets``
    int32[P_pad + 1] phase offsets (padded with the total length),
    ``open_row`` the int32[C, B] row state entering the program.  Returns
    the grouped-order streams the scatter stage consumes plus per-phase
    reductions — every array stays on device.
    """
    Npad = line.shape[0]
    P_pad = offsets.shape[0] - 1
    G = P_pad * C                         # (phase, channel) groups
    idx = jnp.arange(Npad, dtype=jnp.int32)
    # program-order phase: a marker at each phase offset, summed forward
    phase = jnp.cumsum(jnp.zeros(Npad, jnp.int32).at[offsets[1:]].add(
        1, mode="drop"))
    comps = _decode_device(line, spec, banks)
    key = jnp.where(idx < n, phase * C + comps["channel"], G)
    # ---- row-kind classification in bank order ------------------------
    kind_o, bank_o, open_out, idx_o, (key_o, issue_o) = \
        _classify_bank_order(comps, n, open_row, (key, issue))
    # ---- K selection (traced form of choose_block_lanes) --------------
    n_miss = jnp.sum(kind_o != 0)
    K = jnp.where(2 * n_miss < n, BLOCK_LANES, 1).astype(jnp.int32)
    # ---- grouped order: (phase, channel), then program order ----------
    meta_o = ((bank_o % B)
              | ((kind_o != 0).astype(jnp.int32) << 8)
              | ((kind_o == 2).astype(jnp.int32) << 9)
              | ((bank_o < C * B).astype(jnp.int32) << 10))
    key_s, _, meta_s, issue_s = jax.lax.sort(
        (key_o, idx_o, meta_o, issue_o), num_keys=2)
    valid_s = key_s < G
    miss_s = (meta_s & META_MISS) != 0
    bank_s = meta_s & 0xFF
    # ---- block decomposition within (phase, channel) streams ----------
    group_first = key_s != _shift_in(key_s, -1)
    run_start = group_first | miss_s | _shift_in(miss_s, False)
    pos = idx - jax.lax.cummax(jnp.where(run_start, idx, 0))
    lane = pos % K
    # a run's blocks start at pos 0, K, 2K, ...: number them in order
    block_id = jnp.cumsum((lane == 0).astype(jnp.int32)) - 1
    # first block of the current group, propagated forward (block_id is
    # globally non-decreasing in grouped order)
    fb = jax.lax.cummax(jnp.where(group_first, block_id, -1))
    block_rank = block_id - fb
    # bank-rank within (block, bank): K-1 shifted comparisons; blocks
    # never span K lanes, so cross-block pairs compare unequal block ids
    # (which is also why running the widest static loop is K-safe)
    rb = jnp.zeros(Npad, jnp.int32)
    kb = block_id * B + bank_s
    for j in range(1, BLOCK_LANES):
        rb = rb + jnp.concatenate(
            [jnp.zeros(j, jnp.int32),
             (kb[j:] == kb[:-j]).astype(jnp.int32)])
    # ---- steps per phase: each group's block count at its last element
    g_start, g_end = _segments(key_s, G)
    n_blocks = jnp.where(g_end > g_start,
                         block_rank[jnp.maximum(g_end - 1, 0)] + 1, 0)
    L_p = n_blocks.reshape(P_pad, C).max(axis=1)
    step_starts = jnp.cumsum(L_p) - L_p
    S = L_p.sum()
    # each phase's first step, set where the phase starts and carried
    # forward (phases are contiguous and step_starts never decreases)
    p_start = g_start.reshape(P_pad, C)[:, 0]
    r_idx = jax.lax.cummax(jnp.zeros(Npad, jnp.int32).at[p_start].max(
        step_starts, mode="drop")) + block_rank
    # ---- per-phase hits/conflicts: prefix sums read at phase bounds ---
    bounds = jnp.concatenate([p_start, g_end[-1:]])

    def per_phase(x):
        cs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(x.astype(jnp.int32))])[bounds]
        return cs[1:] - cs[:-1]

    hits_p = per_phase(valid_s & ~miss_s)
    confl_p = per_phase((meta_s & META_CONFL) != 0)
    meta_s = meta_s | (rb << META_RB_SHIFT)
    return (r_idx, key_s % C, lane, issue_s, meta_s, valid_s,
            L_p, hits_p, confl_p, open_out, S, K)


@functools.partial(jax.jit, static_argnames=("spec", "banks"))
def _device_row_kinds(line, n, open_row, spec, banks):
    """Program-order int8 row kinds of a padded program (0 on padding):
    the device pack's classification with its bank-order permutation
    undone.  Not on the pack path, which never needs program order."""
    kind_o, _, _, idx_o, _ = _classify_bank_order(
        _decode_device(line, spec, banks), n, open_row, ())
    return jnp.zeros(line.shape[0], jnp.int8).at[idx_o].set(
        kind_o.astype(jnp.int8))


@functools.partial(jax.jit, static_argnames=("S_pad", "C", "K"))
def _device_pack_scatter(r_idx, c_idx, lane, issue_s, meta_s, valid_s,
                         L_p, S_pad, C, K):
    """Scatter the grouped streams into the blocked lockstep
    ``[S_pad, C, K]`` arrays + phase-boundary markers."""
    tgt = jnp.where(valid_s, r_idx, S_pad)
    issue = jnp.zeros((S_pad, C, K), jnp.int32).at[
        tgt, c_idx, lane].set(issue_s, mode="drop")
    meta = jnp.zeros((S_pad, C, K), jnp.int32).at[
        tgt, c_idx, lane].set(meta_s, mode="drop")
    boundary = jnp.zeros(S_pad, bool).at[
        jnp.cumsum(L_p) - 1].set(True, mode="drop")
    return issue, meta, boundary


@jax.jit
def _device_phase_durations(fin, L_p):
    """Per-phase makespans from fused-scan finishes: segmented max of the
    per-step maxima over the phase step ranges (the device counterpart of
    ``finalize_program``'s ``maximum.reduceat``)."""
    step_max = fin.max(axis=(1, 2))
    ends = jnp.cumsum(L_p)
    phase = jnp.searchsorted(
        ends, jnp.arange(fin.shape[0], dtype=jnp.int32), side="right")
    return jnp.zeros(L_p.shape[0], jnp.int32).at[phase].max(
        step_max, mode="drop")


def _fused_scan_core(issue, meta, boundary, timing, carry,
                     banks_per_rank):
    """One scan over a whole multi-phase program, K requests per channel
    per step.

    ``issue/meta`` are ``[S, C, K]`` *blocked* lockstep streams: step
    ``s`` serves every channel's ``s``-th block of the current phase.  A
    block is either up to K consecutive row *hits* (their only timing
    coupling is the per-bank ``bank_avail`` chain — a max-plus recurrence
    the step resolves with one in-step ``cummax`` over the block's
    bank-rank-adjusted issues — and the shared bus, another cummax) or a
    single row miss (which additionally touches the per-rank ACT
    history).  ``boundary[S]`` marks each phase's last step; at a
    boundary the global makespan (max over channels) re-bases the carry
    so the next phase's *phase-relative* issue cycles start from 0 again
    — the in-scan equivalent of the controller's "wait for all memory
    requests, then switch phases".

    The kernel is deliberately gather/scatter-free (XLA CPU executes
    those ~10x slower inside a scan): per-bank state is addressed with
    one-hot masks over the tiny [C, B] arrays.

    Returns ``(finish[S, C, K], carry)``; finishes are relative to their
    phase's start (0 on invalid lanes), so per-phase makespans and stats
    reduce on the host.
    """
    step = make_serve_step(timing, carry[0].shape[0], carry[0].shape[1],
                           carry[3].shape[1], issue.shape[2],
                           banks_per_rank)
    state, fin = jax.lax.scan(step, carry, (issue, meta, boundary))
    return fin, state


def make_serve_step(timing, C, B, R, K, banks_per_rank):
    """Build the blocked lockstep serve step over ``[C, K]`` request
    blocks — the single source of the step semantics, shared verbatim
    by the XLA scan (:func:`_fused_scan_core`) and the Pallas serve
    kernel (``repro.kernels.dram_timing``), so the two ``serve_backend``
    paths cannot drift.

    Returns ``step(state, (iss[C,K], mt[C,K], bnd)) -> (state,
    fin_out[C,K])`` where ``state`` is the 6-tuple in-scan carry
    (persistent lean carry + phase-makespan accumulator).  The
    phase-boundary carry re-base is branchless (``where`` on the
    boundary flag instead of ``lax.cond``): bit-identical, because a
    zero shift is the identity on every carry value (all are
    ``>= NEG_INF32`` by construction), and it is what lets the Pallas
    kernel run the same code without ref-mutating control flow.
    """
    tCL, tRCD, tRP, tRAS, tBL, tRRD, tFAW = (
        timing[i] for i in range(len(TIMING_FIELDS)))
    bank_ids = jnp.arange(B, dtype=jnp.int32)
    rank_ids = jnp.arange(R, dtype=jnp.int32)
    ptr_ids = jnp.arange(4, dtype=jnp.int32)
    lane_ids = jnp.arange(K, dtype=jnp.int32)
    lane_tbl = lane_ids * tBL                              # loop-invariant
    lane_tbl1 = (lane_ids + 1) * tBL

    tril = lane_ids[:, None] >= lane_ids[None, :]          # [K, K]

    def pick(masked, axis):
        return jnp.max(masked, axis=axis)

    def widen(mask, axis):
        # Mosaic cannot reshape i1 vectors: insert the unit axis on int32
        return jnp.expand_dims(mask.astype(jnp.int32), axis) != 0

    def step(state, x):
        avail, act, bus, hist, ptr, pmf = state
        iss, mt, bnd = x                                   # [C, K]
        b = mt & 0xFF
        ms = (mt & META_MISS) != 0
        cf = (mt & META_CONFL) != 0
        v = (mt & META_VALID) != 0
        rb_tbl = ((mt >> META_RB_SHIFT) & META_RB_MASK) * tBL  # rank*tBL
        ohb = b[:, :, None] == bank_ids                    # [C, K, B]
        avail_b = pick(jnp.where(ohb, avail[:, None, :], NEG_INF32), 2)
        act_b = pick(jnp.where(ohb, act[:, None, :], NEG_INF32), 2)
        # --- hit chain: col_r = r*tBL + max(max_{s<=r, same bank}
        #     (iss_s - s*tBL), avail_entry) over the block's lanes.
        #     (Pairwise [K, K] mask; prefix-max reformulations via
        #     lax.cummax and an unrolled shift ladder were measured
        #     slower under XLA CPU at K=8.)
        adj = iss - rb_tbl
        same = (b[:, :, None] == b[:, None, :]) & tril     # [C, K, K]
        own = pick(jnp.where(same, adj[:, None, :], NEG_INF32), 2)
        col_hit = rb_tbl + jnp.maximum(own, avail_b)
        # --- miss machinery at block level (at most one miss per block,
        #     alone in it), so rank/ptr/hist select tiny [C, ...] slices
        mv = ms & v
        m_any = mv.any(axis=1)                             # [C]
        if R == 1:
            ptr_m = ptr[:, 0]                              # [C]
            hist_m = hist[:, 0]                            # [C, 4]
        else:
            rank = b // banks_per_rank
            rank_m = pick(jnp.where(mv, rank, 0), 1)       # [C]
            ohr_m = rank_m[:, None] == rank_ids            # [C, R]
            ptr_m = pick(jnp.where(ohr_m, ptr, 0), 1)
            hist_m = pick(jnp.where(widen(ohr_m, 2), hist, NEG_INF32),
                          1)                               # [C, 4]
        ohp_m = ptr_m[:, None] == ptr_ids                  # [C, 4]
        oh_last = ((ptr_m + 3) % 4)[:, None] == ptr_ids
        hist_p = pick(jnp.where(ohp_m, hist_m, NEG_INF32), 1)
        last_r = pick(jnp.where(oh_last, hist_m, NEG_INF32), 1)
        # ACT rate limits per rank (tRRD, tFAW over the 4th-last ACT)
        floor = jnp.maximum(last_r + tRRD, hist_p + tFAW)  # [C]
        base = jnp.maximum(iss, avail_b)
        pre = jnp.where(cf, jnp.maximum(base, act_b + tRAS) + tRP, base)
        a = jnp.maximum(pre, floor[:, None])               # miss ACT time
        col = jnp.where(ms, a + tRCD, col_hit)
        # --- shared data bus: prefix max over the block's lanes
        cadj = col + tCL - lane_tbl
        ccm = pick(jnp.where(tril & widen(v, 1), cadj[:, None, :],
                             NEG_INF32), 2)
        fin = lane_tbl1 + jnp.maximum(bus[:, None], ccm)
        fin_out = jnp.where(v, fin, jnp.int32(0))
        mx = pick(fin_out, 1)                              # [C]
        # bank_avail/act/hist/bus only ever increase (chains are
        # monotone), so updates are plain maxes — no masked selects
        bus = jnp.maximum(bus, mx)
        pmf = jnp.maximum(pmf, mx)
        vohb = ohb & widen(v, 2)
        avail = jnp.maximum(
            avail,
            pick(jnp.where(vohb, (col + tBL)[:, :, None], NEG_INF32), 1))
        a_m = pick(jnp.where(mv, a, NEG_INF32), 1)         # [C]
        act = jnp.maximum(
            act, pick(jnp.where(ohb & widen(mv, 2), a[:, :, None],
                                NEG_INF32), 1))
        if R == 1:
            hist = jnp.maximum(
                hist, jnp.where(ohp_m & widen(m_any, 1),
                                a_m[:, None], NEG_INF32)[:, None, :])
            ptr = jnp.where(widen(m_any, 1), (ptr_m + 1)[:, None] % 4,
                            ptr)
        else:
            hist = jnp.maximum(
                hist, jnp.where(
                    (widen(ohr_m, 2) & widen(ohp_m, 1))
                    & widen(m_any, (1, 2)),
                    a_m[:, None, None], NEG_INF32))
            ptr = jnp.where(ohr_m & widen(m_any, 1),
                            ((ptr_m + 1) % 4)[:, None], ptr)

        # branchless phase-boundary re-base: shift = 0 off-boundary is
        # the identity (every carry value is >= NEG_INF32)
        shift = jnp.where(bnd, jnp.max(pmf), jnp.int32(0))
        avail, act, bus, hist = _lean_rebase(avail, act, bus, hist,
                                             shift)
        pmf = jnp.where(bnd, jnp.zeros_like(pmf), pmf)
        return (avail, act, bus, hist, ptr, pmf), fin_out

    return step


def _host_fin(fin):
    """A chunk's finish array read back to the host."""
    with obs.span(obs.DEVICE_WAIT):
        return np.asarray(fin)


def _concat_fins(fins, as_numpy, axis=0):
    """Join per-chunk finish arrays on the requested side of the
    host/device boundary (shared epilogue of the fused-scan wrappers)."""
    if len(fins) == 1:
        return fins[0]
    if as_numpy:
        return np.concatenate(fins, axis=axis)
    return jnp.concatenate(fins, axis=axis)


#: fixed scan-chunk sizes (steps).  A program runs as a few dispatches of
#: these two shapes instead of one dispatch of a bespoke shape: the scan
#: carry chains across chunks bit-exactly, and the jit cache holds TWO
#: compiled scans per DRAM structure for the life of the process — no
#: per-program-length recompilation.
CHUNK_LADDER = (1 << 13, 1 << 17)


def plan_chunks(n_steps: int):
    """Greedy chunk plan covering ``n_steps``: large chunks, then small
    ones (the tail pads to at most ``CHUNK_LADDER[0]`` wasted steps)."""
    small, large = CHUNK_LADDER
    n_large, rem = divmod(n_steps, large)
    n_small = -(-rem // small) if rem else 0
    return [large] * n_large + [small] * n_small


@jax.jit
def _fused_scan(issue, meta, boundary, timing, carry):
    banks_per_rank = carry[0].shape[1] // carry[3].shape[1]
    return _fused_scan_core(issue, meta, boundary, timing, carry,
                            banks_per_rank)


def fused_scan(issue, meta, boundary, timing, carry, as_numpy=True,
               backend="scan"):
    """Serve a whole packed program: a handful of fixed-shape jitted
    dispatches (see :data:`CHUNK_LADDER`), state chained across chunks.

    ``carry`` is the 5-tuple persistent lean carry; the transient
    phase-makespan accumulator is managed here (programs end on a phase
    boundary, where it is zero by construction).  ``as_numpy=False``
    keeps the finish array on device (the device-packed path reduces it
    there; nothing round-trips through the host).

    ``backend`` selects the serve implementation per
    :func:`resolve_serve_backend`: the XLA scan or the Pallas kernel
    (``repro.kernels.dram_timing.ops.dram_serve``) — bit-identical, both
    run :func:`make_serve_step`; the choice is purely an execution-speed
    knob.
    """
    backend = resolve_serve_backend(backend)
    if backend == "pallas":
        # lazy: ref.py in the kernel package imports this module
        from repro.kernels.dram_timing.ops import dram_serve
    C = issue.shape[1]
    state = tuple(carry) + (jnp.zeros((C,), dtype=jnp.int32),)
    timing = jnp.asarray(timing, dtype=jnp.int32)
    banks_per_rank = carry[0].shape[1] // carry[3].shape[1]
    K = issue.shape[2]
    fins = []
    pos = 0
    with obs.span(obs.SERVE):
        for size in plan_chunks(issue.shape[0]):
            chunk = (jnp.asarray(issue[pos:pos + size]),
                     jnp.asarray(meta[pos:pos + size]),
                     jnp.asarray(boundary[pos:pos + size]))
            obs.count("serve_lane_slots", size * C * K)
            if backend == "pallas":
                count_dispatch("pallas")
                fin, state = dram_serve(*chunk, timing, state,
                                        banks_per_rank=banks_per_rank)
            else:
                count_dispatch("fused")
                fin, state = _fused_scan(*chunk, timing, state)
            fins.append(_host_fin(fin) if as_numpy else fin)
            pos += size
        return _concat_fins(fins, as_numpy), state[:5]


@jax.jit
def _fused_scan_batch(issue, meta, boundary, timing, carry):
    banks_per_rank = carry[0].shape[2] // carry[3].shape[2]
    return jax.vmap(
        lambda i, mt, bd, tm, c: _fused_scan_core(
            i, mt, bd, tm, c, banks_per_rank)
    )(issue, meta, boundary, timing, carry)


@jax.jit
def _fused_scan_batch_shared(issue, meta, boundary, timing, carry):
    """Batch over timings/carries with the program streams SHARED
    (``in_axes=None``): every stream-only term of the step — the block
    masks and the O(K^2) hit-chain resolution — is computed once for the
    whole batch instead of per case, and the blocked arrays are never
    replicated M-fold."""
    banks_per_rank = carry[0].shape[2] // carry[3].shape[2]
    return jax.vmap(
        lambda tm, c: _fused_scan_core(issue, meta, boundary, tm, c,
                                       banks_per_rank),
        in_axes=(0, 0))(timing, carry)


def fused_scan_batch(issue, meta, boundary, timing, n_banks,
                     banks_per_rank, as_numpy=True):
    """Batched fused scan: leading axis = memory/case batch; each chunk
    dispatch serves every case in the batch
    (``sweep(batch_memories=True)``)."""
    M, S, C, K = issue.shape
    single = init_lean_carry(C, n_banks, banks_per_rank)
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (M,) + x.shape),
        single + (jnp.zeros((C,), dtype=jnp.int32),))
    timing = jnp.asarray(timing, dtype=jnp.int32)
    fins = []
    pos = 0
    with obs.span(obs.SERVE):
        for size in plan_chunks(S):
            count_dispatch("fused_batch")
            obs.count("serve_lane_slots", M * size * C * K)
            fin, state = _fused_scan_batch(
                jnp.asarray(issue[:, pos:pos + size]),
                jnp.asarray(meta[:, pos:pos + size]),
                jnp.asarray(boundary[:, pos:pos + size]), timing, state)
            fins.append(_host_fin(fin) if as_numpy else fin)
            pos += size
        return _concat_fins(fins, as_numpy, axis=1), state[:5]


def fused_scan_batch_shared(issue, meta, boundary, timing, n_banks,
                            banks_per_rank, as_numpy=True):
    """Serve ONE packed program against a batch of timing vectors
    (``timing`` is int32[M, 7]) — the cache-hit fast path of
    ``sweep(batch_memories=True)`` on a geometry-shared memory grid.
    Returns ``(finish[M, S, C, K], states)`` like
    :func:`fused_scan_batch`, but the program streams are traced
    unbatched, so the stream-only step terms are case-invariant and the
    blocked arrays transfer once, not M times."""
    M = timing.shape[0]
    S, C, K = issue.shape
    single = init_lean_carry(C, n_banks, banks_per_rank)
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (M,) + x.shape),
        single + (jnp.zeros((C,), dtype=jnp.int32),))
    timing = jnp.asarray(timing, dtype=jnp.int32)
    fins = []
    pos = 0
    with obs.span(obs.SERVE):
        for size in plan_chunks(S):
            count_dispatch("fused_batch")
            obs.count("serve_lane_slots", M * size * C * K)
            fin, state = _fused_scan_batch_shared(
                jnp.asarray(issue[pos:pos + size]),
                jnp.asarray(meta[pos:pos + size]),
                jnp.asarray(boundary[pos:pos + size]), timing, state)
            fins.append(_host_fin(fin) if as_numpy else fin)
            pos += size
        return _concat_fins(fins, as_numpy, axis=1), state[:5]


def simulate_trace_jax(
    trace: Trace, cfg: DRAMConfig, keep_finish: bool = False,
) -> timing_mod.TraceResult:
    """Drop-in replacement for :func:`repro.core.timing.simulate_trace`."""
    if len(trace) == 0:
        return timing_mod.simulate_trace(trace.line_addr, trace.issue, cfg)
    packed = pack_channels(trace, cfg)
    finish, kind, _ = simulate_packed(
        packed.issue, packed.bank, packed.row, packed.valid,
        timing_params(cfg.timing), cfg.banks_per_channel, cfg.org.banks,
    )
    finish = np.asarray(finish)
    kind = np.asarray(kind)
    v = packed.valid
    finish_flat = np.zeros(len(trace), dtype=np.int64)
    finish_flat[packed.scatter_index[v]] = finish[v]
    cycles = int(finish_flat.max())
    ns = cycles / cfg.clock_ghz
    total_bytes = len(trace) * CACHE_LINE_BYTES
    per_channel = {
        c: (int(finish[c][v[c]].max()) if v[c].any() else 0)
        for c in range(cfg.channels)
    }
    return timing_mod.TraceResult(
        cycles=cycles,
        ns=ns,
        total_requests=len(trace),
        total_bytes=total_bytes,
        row_hits=int((kind == 0).sum()),
        row_empty=int((kind == 1).sum()),
        row_conflicts=int((kind == 2).sum()),
        achieved_gbps=(total_bytes / ns) if ns > 0 else 0.0,
        peak_gbps=cfg.peak_gbps,
        per_channel_cycles=per_channel,
        finish=finish_flat if keep_finish else None,
    )
