"""Shared machinery for the vectorized accelerator trace models.

``VectorizedDRAM`` runs phases (scatter, gather, per-iteration barriers)
through the JAX scan model while carrying per-channel DRAM state across
phases — the vectorized equivalent of the paper's controller "waiting on
all memory requests to finish before switching phases": the next phase's
traces are issued no earlier than the previous phase's makespan.

Two execution modes share one statistics surface:

* :meth:`VectorizedDRAM.run_program` — the fused whole-run pipeline: a
  :class:`~repro.core.trace.SegmentedTrace` (every phase of the
  simulation, emitted up front by the trace models) is packed once and
  served by a blocked jitted scan that honors the phase barriers
  internally.  This is the default fast path: a handful of fixed-shape
  chunk dispatches per run instead of two dispatches per iteration.
* :meth:`VectorizedDRAM.run_phase` — the legacy incremental path (one
  dispatch per phase), kept for interactive/streaming use and as the
  bit-equivalence reference for the fused scan.

When the device carries an on-chip hierarchy level
(``DRAMConfig.cache``), both modes first run the program through the
cache filter (:mod:`repro.core.cache`): hits are dropped *before*
packing and the prefetcher shapes issue lower bounds, with the lookup
state persisting across phases and programs.  The filtered program is
what packs — which is why ``DRAMConfig.geometry_key`` includes the cache
dimension.

Programs are padded to a two-size chunk ladder so the process compiles
each scan structure exactly twice, whatever the run length; DRAM timing
parameters are traced inputs, so DDR3/DDR4/HBM2/HBM2E all share one
compiled scan.

Packing itself has two backends: the jitted *device* pack
(:func:`pack_program_device` — decode, row-kind classification, and the
block decomposition as fixed-shape bucketed dispatches whose outputs feed
the fused scan without materializing on the host, transfers narrowed to
int32) and the NumPy *host* pack (:func:`pack_program`, the
bit-equivalence reference).  Packing depends only on DRAM *geometry*
(``DRAMConfig.geometry_key``) and the program, never on timing — which is
what lets the sweep engine cache packed programs across a
timing-comparison grid.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import cache as cache_mod
from repro.core.dram import DRAMConfig, CACHE_LINE_BYTES
from repro.core.trace import SegmentedTrace, Trace
from repro.core import vectorized as vec


def _bucket(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


@dataclasses.dataclass
class PhaseStats:
    name: str
    requests: int
    bytes: int
    start_cycle: int
    end_cycle: int
    row_hits: int
    row_conflicts: int


#: lanes per block in the fused scan (requests per channel per step);
#: hit-heavy programs use wide blocks, conflict-heavy ones serialize.
#: (Defined in ``core.vectorized`` so the device pack kernels share it.)
BLOCK_LANES = vec.BLOCK_LANES


@dataclasses.dataclass(frozen=True)
class PackedProgram:
    """A :class:`SegmentedTrace` packed for the fused scan: blocked
    lockstep ``[S, C, K]`` per-channel streams with phase boundary
    markers and host-precomputed row-buffer kinds.

    A block (one step of one channel) is up to K consecutive row hits —
    whose per-bank chains the scan step resolves internally — or a single
    row miss; the block decomposition is what shrinks the sequential
    scan length by ~K on the row-hit-dominated streams the paper's
    accelerators produce."""

    issue: np.ndarray        # int32[S, C, K] (phase-relative)
    meta: np.ndarray         # int32[S, C, K] packed bank/kind/rank word
    boundary: np.ndarray     # bool[S]
    timing: np.ndarray       # int32[7]
    n_banks: int
    banks_per_rank: int
    names: List[str]
    requests: np.ndarray     # int64[P] per-phase request counts
    offsets: np.ndarray      # int64[P+1] per-phase request offsets
    kind: np.ndarray         # int8[N] per-request row kind, program order
    step_starts: np.ndarray  # int64[P] first lockstep step of each phase
    n_steps: int             # S before padding
    open_row_final: np.ndarray  # int32[C, B] row state after the program

    @property
    def n_phases(self) -> int:
        return len(self.names)

    @property
    def signature(self):
        """Compiled-shape signature: programs with equal signatures share
        one compiled fused scan (and can batch, see ``fused_scan_batch``)."""
        return (self.issue.shape, self.n_banks, self.banks_per_rank)


def classify_rows(bank_global: np.ndarray, row: np.ndarray,
                  open_row: np.ndarray):
    """Row-buffer kinds (0 hit / 1 empty / 2 conflict) for a program-order
    stream, given the per-bank open-row state entering the stream.

    The classification depends only on each bank's row *sequence* — never
    on timing — which is what lets the fused scan skip row tracking.
    Returns ``(kind int8[N], open_row_after flat int64[C*B])``.
    """
    flat = np.asarray(open_row, dtype=np.int64).ravel().copy()
    if len(flat) < (1 << 15):
        # small key range: radix argsort (~5x over int64 mergesort)
        order = np.argsort(bank_global.astype(np.int16), kind="stable")
    else:
        order = np.argsort(bank_global, kind="stable")
    gbo = bank_global[order]
    rows_o = row[order]
    prev = np.empty(len(order), dtype=np.int64)
    first = np.empty(len(order), dtype=bool)
    first[:1] = True
    first[1:] = gbo[1:] != gbo[:-1]
    prev[1:] = rows_o[:-1]
    prev[first] = flat[gbo[first]]
    kind_o = np.where(prev == rows_o, 0,
                      np.where(prev == -1, 1, 2)).astype(np.int8)
    kind = np.empty(len(order), dtype=np.int8)
    kind[order] = kind_o
    last = np.empty(len(order), dtype=bool)
    last[:-1] = gbo[:-1] != gbo[1:]
    last[-1:] = True
    flat[gbo[last]] = rows_o[last]
    return kind, flat


def pack_program(program: SegmentedTrace, cfg: DRAMConfig,
                 open_row: Optional[np.ndarray] = None
                 ) -> Optional[PackedProgram]:
    """Pack a whole-run program for the fused scan (one decode + one
    stable argsort; no per-phase or per-channel Python loops).

    ``open_row`` is the int[C, B] row state entering the program
    (default: all banks closed)."""
    P = program.n_phases
    if P == 0 or len(program) == 0:
        return None
    if np.any(program.issue < 0) or np.any(
            program.issue >= vec.MAX_PHASE_ISSUE):
        raise ValueError("issue cycles out of int32 range; chunk the trace")
    comps = cfg.decode_lines(program.line_addr)
    ch = comps["channel"]
    C = cfg.channels
    B = cfg.banks_per_channel
    if B > 256:
        raise ValueError(
            f"banks_per_channel={B} exceeds the fused scan's 8-bit bank "
            f"field; use the per-phase backend for this device")
    if open_row is None:
        open_row = np.full((C, B), -1, dtype=np.int64)
    kind, open_flat = classify_rows(comps["bank_global"], comps["row"],
                                    open_row)
    requests = np.diff(program.offsets)
    phase = np.repeat(np.arange(P, dtype=np.int64), requests)
    key = phase * C + ch
    # hit-dominated streams get wide blocks; conflict-heavy ones (where
    # almost every block would be a singleton miss anyway) serialize.
    K = vec.choose_block_lanes(int((kind != 0).sum()), len(kind))
    # ---- block decomposition within each (phase, channel) stream ------
    # grouped order: phase-major, channel, then program order
    order = np.argsort(key, kind="stable")
    miss_g = kind[order] != 0
    group_first = np.empty(len(order), dtype=bool)
    group_first[:1] = True
    group_first[1:] = key[order][1:] != key[order][:-1]
    run_start = group_first | miss_g
    run_start[1:] |= miss_g[:-1]
    run_id = np.cumsum(run_start) - 1
    run_len = np.bincount(run_id)
    run_off = np.cumsum(run_len) - run_len
    pos = np.arange(len(order), dtype=np.int64) - run_off[run_id]
    lane = pos % K
    blocks_per_run = (run_len + K - 1) // K
    block_off = np.cumsum(blocks_per_run) - blocks_per_run
    block_id = block_off[run_id] + pos // K      # global, grouped order
    # block rank within its (phase, channel) group
    first_block = block_id[group_first]
    gid = np.cumsum(group_first) - 1
    block_rank = block_id - first_block[gid]
    # bank-rank within (block, bank): K-1 shifted comparisons on the
    # fused (block, bank) key
    bank_g = comps["bank_in_channel"][order]
    rb = np.zeros(len(order), dtype=np.int32)
    if K > 1:
        kb = block_id * B + bank_g
        for j in range(1, K):
            rb[j:] += kb[j:] == kb[:-j]
    # steps per phase = max block count over channels (block_rank is
    # non-decreasing within a group, so each group's last element has it)
    group_last = np.empty(len(order), dtype=bool)
    group_last[:-1] = group_first[1:]
    group_last[-1:] = True
    n_blocks_g = np.zeros(P * C, dtype=np.int64)
    n_blocks_g[key[order][group_last]] = block_rank[group_last] + 1
    L_p = n_blocks_g.reshape(P, C).max(axis=1)
    step_starts = np.cumsum(L_p) - L_p
    S = int(L_p.sum())
    S_pad = sum(vec.plan_chunks(S))
    r_idx = step_starts[phase[order]] + block_rank
    c_idx = ch[order]
    issue = np.zeros((S_pad, C, K), dtype=np.int32)
    meta = np.zeros((S_pad, C, K), dtype=np.int32)
    issue[r_idx, c_idx, lane] = program.issue[order]
    meta[r_idx, c_idx, lane] = vec.pack_meta(
        bank_g, miss_g, kind[order] == 2,
        np.ones(len(order), dtype=bool), bank_rank=rb)
    boundary = np.zeros(S_pad, dtype=bool)
    boundary[np.cumsum(L_p) - 1] = True
    return PackedProgram(
        issue=issue, meta=meta, boundary=boundary,
        timing=vec.timing_params(cfg.timing),
        n_banks=B, banks_per_rank=cfg.org.banks,
        names=list(program.names), requests=requests,
        offsets=np.asarray(program.offsets), kind=kind,
        step_starts=step_starts, n_steps=S,
        open_row_final=open_flat.reshape(C, B))


@dataclasses.dataclass(frozen=True)
class DevicePackedProgram:
    """A program packed *on the device* by the jitted pack path: the
    blocked ``[S, C, K]`` streams live as device arrays and feed the
    fused scan without ever materializing on the host.  Bit-identical to
    :class:`PackedProgram` (``pack_program`` is the NumPy reference; the
    parity is tested field by field), with the per-request row kinds
    pre-reduced to per-phase hit/conflict counts so finalization only
    transfers ``O(P)`` integers."""

    issue: object            # int32[S, C, K] device
    meta: object             # int32[S, C, K] device
    boundary: object         # bool[S] device
    timing: np.ndarray       # int32[7] (host; traced into the scan)
    n_banks: int
    banks_per_rank: int
    names: List[str]
    requests: np.ndarray     # int64[P]
    offsets: np.ndarray      # int64[P+1]
    kind: object             # int8[Npad] device (program order; tests)
    L_p: object              # int32[P_pad] device steps-per-phase
    hits_p: object           # int32[P_pad] device per-phase row hits
    confl_p: object          # int32[P_pad] device per-phase conflicts
    n_steps: int             # S before padding
    open_row_final: object   # int32[C, B] device row state after the run

    @property
    def n_phases(self) -> int:
        return len(self.names)

    @property
    def signature(self):
        return (tuple(self.issue.shape), self.n_banks,
                self.banks_per_rank)


def device_pack_supported(program: SegmentedTrace,
                          cfg: DRAMConfig) -> bool:
    """Whether the jitted device pack path can serve this program: pow2
    address components, <=256 banks/channel, and every index/address in
    int32 range (the host packer covers the rest)."""
    if cfg.decode_spec() is None:
        return False
    if cfg.banks_per_channel > 256:
        return False
    n = len(program)
    if n == 0:
        return True
    # kb = block_id * B + bank must stay in int32 (block_id < n)
    if n * cfg.banks_per_channel >= 2**31:
        return False
    return int(program.line_addr.max()) < 2**31


def pack_program_device(program: SegmentedTrace, cfg: DRAMConfig,
                        open_row=None) -> Optional[DevicePackedProgram]:
    """Pack a whole-run program on device (see the device-pack section of
    :mod:`repro.core.vectorized`).  Two fixed-shape jitted dispatches —
    classify + block-decompose, then the lockstep scatter — with one tiny
    scalar sync in between (the step count picks the chunk-ladder
    padding).  ``open_row`` may be a host or device int[C, B] array."""
    P = program.n_phases
    N = len(program)
    if P == 0 or N == 0:
        return None
    if np.any(program.issue < 0) or np.any(
            program.issue >= vec.MAX_PHASE_ISSUE):
        raise ValueError("issue cycles out of int32 range; chunk the trace")
    C = cfg.channels
    B = cfg.banks_per_channel
    spec = cfg.decode_spec()
    N_pad = _bucket(N)
    P_pad = _bucket(P)
    line32 = np.zeros(N_pad, dtype=np.int32)
    line32[:N] = program.line_addr
    issue32 = np.zeros(N_pad, dtype=np.int32)
    issue32[:N] = program.issue
    offsets32 = np.full(P_pad + 1, N, dtype=np.int32)
    offsets32[:P + 1] = program.offsets
    if open_row is None:
        open_row = jnp.full((C, B), -1, dtype=jnp.int32)
    else:
        open_row = jnp.asarray(open_row, dtype=jnp.int32)
    vec.count_dispatch("device_pack")
    (r_idx, c_idx, lane, issue_s, meta_s, valid_s, L_p, hits_p,
     confl_p, kind, open_out, S, K) = vec._device_pack_core(
        jnp.asarray(line32), jnp.asarray(issue32),
        jnp.asarray(offsets32), jnp.int32(N), open_row,
        spec=spec, C=C, B=B, banks=cfg.org.banks)
    with obs.span(obs.DEVICE_WAIT):
        S = int(S)
        K = int(K)
    S_pad = sum(vec.plan_chunks(S))
    issue_d, meta_d, boundary_d = vec._device_pack_scatter(
        r_idx, c_idx, lane, issue_s, meta_s, valid_s, L_p,
        S_pad=S_pad, C=C, K=K)
    requests = np.diff(program.offsets)
    return DevicePackedProgram(
        issue=issue_d, meta=meta_d, boundary=boundary_d,
        timing=vec.timing_params(cfg.timing),
        n_banks=B, banks_per_rank=cfg.org.banks,
        names=list(program.names), requests=requests,
        offsets=np.asarray(program.offsets), kind=kind,
        L_p=L_p, hits_p=hits_p, confl_p=confl_p, n_steps=S,
        open_row_final=open_out)


def _auto_pack_prefers_device() -> bool:
    """The ``"auto"`` policy: pack on device when there is a real
    host->device boundary to avoid (TPU/GPU — the jitted pack keeps the
    blocked streams device-resident and halves the transfer to int32).
    On the CPU backend "device" memory IS host memory and XLA's sorts
    lose to NumPy's radix paths, so auto stays with the host packer.
    Override per backend instance (``pack_backend="device"``) or
    globally with ``REPRO_PACK_BACKEND=device|host``."""
    env = os.environ.get("REPRO_PACK_BACKEND")
    if env in ("device", "host"):
        return env == "device"
    return jax.default_backend() != "cpu"


def pack_program_auto(program: SegmentedTrace, cfg: DRAMConfig,
                      open_row=None, backend: str = "auto"):
    """Pack with the requested backend: ``"device"`` (jitted JAX path),
    ``"host"`` (the NumPy reference), or ``"auto"`` (platform heuristic,
    see :func:`_auto_pack_prefers_device`; host whenever the device path
    does not support the program/geometry)."""
    with obs.span(obs.PACK):
        if backend == "auto":
            backend = ("device" if _auto_pack_prefers_device() else "host")
            if backend == "device" and not device_pack_supported(program,
                                                                cfg):
                backend = "host"
        if backend == "host":
            if open_row is not None:
                open_row = np.asarray(open_row)
            return pack_program(program, cfg, open_row=open_row)
        if not device_pack_supported(program, cfg):
            raise ValueError(
                "program/device not eligible for the device pack path "
                "(non-pow2 geometry, >256 banks, or addresses beyond int32)")
        return pack_program_device(program, cfg, open_row=open_row)


@dataclasses.dataclass
class ProgramStats:
    """Accumulated DRAM statistics of one executed program — the shared
    surface :class:`~repro.core.accel.SimReport` assembly reads (duck-typed
    with ``VectorizedDRAM`` / ``EventDRAM``).  The cache fields describe
    the on-chip hierarchy level the program passed through before packing
    (zero when no cache is configured)."""

    phases: List[PhaseStats]
    now: int
    total_requests: int
    total_row_hits: int
    total_row_conflicts: int
    cache_lookups: int = 0
    cache_hits: int = 0
    prefetch_hits: int = 0

    def attach_cache(self, cs) -> "ProgramStats":
        """Fold a :class:`repro.core.cache.CacheStats` into this surface
        (the sweep engine serves cached packs whose filtering happened at
        pack time)."""
        if cs is not None:
            self.cache_lookups += cs.lookups
            self.cache_hits += cs.hits
            self.prefetch_hits += cs.prefetch_hits
        return self


def finalize_program(packed: PackedProgram, finish,
                     origin: int = 0) -> ProgramStats:
    """Turn the fused scan's per-step finishes into phase statistics.

    ``finish[s, c]`` is relative to the owning phase's start (0 on
    invalid lanes), so each phase's makespan is a segmented max; row
    hits/conflicts reduce from the host-precomputed kinds.  The absolute
    clock is the running (int64, overflow-free) sum of makespans."""
    with obs.span(obs.REDUCE):
        with obs.span(obs.DEVICE_WAIT):
            finish = np.asarray(finish)
        P = packed.n_phases
        fin = finish[:packed.n_steps].max(axis=(1, 2))
        dur = np.maximum.reduceat(fin, packed.step_starts).astype(np.int64)
        off = packed.offsets[:-1]
        hits = np.add.reduceat((packed.kind == 0).astype(np.int64), off)
        confl = np.add.reduceat((packed.kind == 2).astype(np.int64), off)
        ends = origin + np.cumsum(dur)
        starts = ends - dur
        phases = [
            PhaseStats(
                name=packed.names[p], requests=int(packed.requests[p]),
                bytes=int(packed.requests[p]) * CACHE_LINE_BYTES,
                start_cycle=int(starts[p]), end_cycle=int(ends[p]),
                row_hits=int(hits[p]), row_conflicts=int(confl[p]),
            )
            for p in range(P)
        ]
        return ProgramStats(
            phases=phases, now=int(ends[-1]) if P else origin,
            total_requests=int(packed.requests.sum()),
            total_row_hits=int(hits.sum()),
            total_row_conflicts=int(confl.sum()),
        )


def finalize_program_device(packed: DevicePackedProgram, finish,
                            origin: int = 0) -> ProgramStats:
    """Device-path counterpart of :func:`finalize_program`: per-phase
    makespans reduce on device (``finish`` is the device finish array the
    fused scan produced with ``as_numpy=False``); only ``O(P)`` integers
    cross to the host."""
    with obs.span(obs.REDUCE):
        dur = vec._device_phase_durations(finish, packed.L_p)
        with obs.span(obs.DEVICE_WAIT):
            dur = np.asarray(dur)
        P = packed.n_phases
        dur = dur[:P].astype(np.int64)
        hits = np.asarray(packed.hits_p)[:P].astype(np.int64)
        confl = np.asarray(packed.confl_p)[:P].astype(np.int64)
        ends = origin + np.cumsum(dur)
        starts = ends - dur
        phases = [
            PhaseStats(
                name=packed.names[p], requests=int(packed.requests[p]),
                bytes=int(packed.requests[p]) * CACHE_LINE_BYTES,
                start_cycle=int(starts[p]), end_cycle=int(ends[p]),
                row_hits=int(hits[p]), row_conflicts=int(confl[p]),
            )
            for p in range(P)
        ]
        return ProgramStats(
            phases=phases, now=int(ends[-1]) if P else origin,
            total_requests=int(packed.requests.sum()),
            total_row_hits=int(hits.sum()),
            total_row_conflicts=int(confl.sum()),
        )


def serve_packed(packed, timing=None, carry=None,
                 origin: int = 0, serve_backend: str = "scan"):
    """Run one packed program (host- or device-packed) through the fused
    scan from the given carry (default: cold DRAM state) and reduce it to
    :class:`ProgramStats`.  Returns ``(stats, lean_carry)``.

    ``timing`` overrides the timing vector packed with the program — this
    is what lets a geometry-keyed cached pack replay against any traced
    timing (the pack itself never depends on timing).  ``serve_backend``
    picks the fused-scan implementation (XLA scan or the Pallas serve
    kernel — bit-identical; see ``vec.resolve_serve_backend``).
    """
    if timing is None:
        timing = packed.timing
    C = packed.issue.shape[1]
    if carry is None:
        carry = vec.init_lean_carry(C, packed.n_banks,
                                    packed.banks_per_rank)
    device = isinstance(packed, DevicePackedProgram)
    fin, lean = vec.fused_scan(packed.issue, packed.meta,
                               packed.boundary, timing, carry,
                               as_numpy=not device,
                               backend=serve_backend)
    if device:
        return finalize_program_device(packed, fin, origin=origin), lean
    return finalize_program(packed, fin, origin=origin), lean


class VectorizedDRAM:
    """Stateful multi-phase DRAM simulation (JAX fast path).

    ``pack_backend`` selects how :meth:`run_program` packs: ``"auto"``
    (device-resident jitted pack when the device/program is eligible,
    NumPy otherwise), ``"host"`` (always the NumPy reference packer), or
    ``"device"`` (force the jitted path; raises when unsupported).  Both
    produce bit-identical scans and statistics.

    The serve side is governed by ``cfg.serve_backend``
    (``auto|scan|pallas``): the XLA fused scan or the Pallas serve
    kernel, also bit-identical — both knobs trade execution speed only.
    """

    def __init__(self, cfg: DRAMConfig, pack_backend: str = "auto"):
        if pack_backend not in ("auto", "host", "device"):
            raise ValueError(
                f"pack_backend must be auto|host|device, "
                f"got {pack_backend!r}")
        self.cfg = cfg
        self.pack_backend = pack_backend
        # resolve once: auto -> scan|pallas for this process's platform
        self.serve_backend = vec.resolve_serve_backend(
            getattr(cfg, "serve_backend", "auto"))
        self._timing = vec.timing_params(cfg.timing)
        # on-chip hierarchy level: requests are filtered through it (hits
        # dropped, prefetch issue shaping) before they reach the packer;
        # the lookup state persists across phases and programs.
        self.cache = cfg.effective_cache
        self._cache_state = cache_mod.init_state(self.cache)
        self.cache_stats = cache_mod.CacheStats()
        self._reset_carry()
        # Device-side cycle math is int32; ``_origin`` (host int64) anchors
        # the device-relative clock so runs can exceed the int32 range
        # without losing accumulated statistics or absolute time.
        self._origin = 0
        self._rel_now = 0
        self.phases: List[PhaseStats] = []
        self.total_requests = 0
        self.total_row_hits = 0
        self.total_row_conflicts = 0

    def _reset_carry(self) -> None:
        C = self.cfg.channels
        single = vec.init_channel_carry(self.cfg.banks_per_channel,
                                        self.cfg.org.banks)
        self.carry = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (C,) + x.shape), single
        )

    @property
    def now(self) -> int:
        """Current absolute memory-clock cycle."""
        return self._origin + self._rel_now

    def _record(self, name: str, requests: int, start: int, end: int,
                hits: int, confl: int) -> None:
        self.phases.append(PhaseStats(
            name=name, requests=requests,
            bytes=requests * CACHE_LINE_BYTES,
            start_cycle=start, end_cycle=end,
            row_hits=hits, row_conflicts=confl,
        ))
        self.total_requests += requests
        self.total_row_hits += hits
        self.total_row_conflicts += confl

    # the SimReport assembly reads these off any stats surface
    @property
    def cache_lookups(self) -> int:
        return self.cache_stats.lookups

    @property
    def cache_hits(self) -> int:
        return self.cache_stats.hits

    @property
    def prefetch_hits(self) -> int:
        return self.cache_stats.prefetch_hits

    def run_phase(self, trace: Trace, name: str = "phase") -> int:
        """Simulate one phase starting at the current clock; returns its
        makespan (absolute memory cycle)."""
        if self.cache is not None:
            trace, cs, self._cache_state = cache_mod.filter_trace(
                trace, self.cache, self._cache_state)
            self.cache_stats.merge(cs)
        if len(trace) == 0:
            return self.now
        start_rel = self._rel_now
        issue = trace.issue + start_rel
        if issue.max() >= vec.MAX_PHASE_ISSUE:
            # Re-base the device clock: phases are serialized, so the
            # carried times' common offset folds into ``_origin``.
            # Simplest safe approach: flush the carry (rows stay open is
            # a <1% effect at this magnitude) — accumulated statistics
            # and the absolute clock are preserved.
            self._origin += self._rel_now
            self._rel_now = 0
            self._reset_carry()
            start_rel = 0
            issue = trace.issue
        cfg = self.cfg
        comps = cfg.decode_lines(trace.line_addr)
        ch = comps["channel"]
        C = cfg.channels
        counts = np.bincount(ch, minlength=C)
        L = _bucket(int(counts.max()))
        issue_p, bank_p, row_p, valid_p, _ = vec.pack_streams(
            ch, issue, comps["bank_in_channel"], comps["row"], C, L)
        finish, kind, self.carry = vec.simulate_packed(
            issue_p, bank_p, row_p, valid_p, self._timing,
            cfg.banks_per_channel, cfg.org.banks, self.carry,
        )
        finish = np.asarray(finish)
        kind = np.asarray(kind)
        end_rel = int(finish[valid_p].max())
        self._record(name, len(trace), self._origin + start_rel,
                     self._origin + end_rel,
                     int((kind == 0).sum()), int((kind == 2).sum()))
        self._rel_now = max(self._rel_now, end_rel)
        return self._origin + end_rel

    def run_program(self, program: SegmentedTrace) -> int:
        """Serve a whole multi-phase program in a handful of jitted
        dispatches (device-resident pack + fused scan with the phase
        barriers honored inside it); returns the final absolute makespan.
        Bit-equivalent to calling :meth:`run_phase` per phase."""
        if self.cache is not None:
            program, cs, self._cache_state = cache_mod.filter_program(
                program, self.cache, self._cache_state)
            self.cache_stats.merge(cs)
        packed = pack_program_auto(program, self.cfg,
                                   open_row=self.carry[0],
                                   backend=self.pack_backend)
        if packed is None:
            return self.now
        if self._rel_now:
            # Fold the running clock into the origin (exact shift, no
            # flush) so the program's phase-relative issues line up.
            self.carry = vec.rebase_carry(self.carry,
                                          jnp.int32(self._rel_now))
            self._origin += self._rel_now
            self._rel_now = 0
        stats, lean = serve_packed(packed, timing=self._timing,
                                   carry=vec.lean_from_full(self.carry),
                                   origin=self._origin,
                                   serve_backend=self.serve_backend)
        self.carry = vec.full_from_lean(lean, packed.open_row_final)
        self.phases.extend(stats.phases)
        self.total_requests += stats.total_requests
        self.total_row_hits += stats.total_row_hits
        self.total_row_conflicts += stats.total_row_conflicts
        # the fused scan re-bases at every barrier: the carry is relative
        # to the final makespan, which becomes the new origin.
        self._origin = stats.now
        self._rel_now = 0
        return self.now


@dataclasses.dataclass
class SimReport:
    """Result of one accelerator simulation run."""

    system: str
    problem: str
    graph: str
    runtime_ns: float
    iterations: int
    edges: int
    vertices: int
    total_requests: int
    total_bytes: int
    row_hit_rate: float
    phases: List[PhaseStats]
    # on-chip hierarchy level (all zero when no cache is configured);
    # ``total_requests`` counts what reached DRAM *after* filtering.
    cache_lookups: int = 0
    cache_hits: int = 0
    prefetch_hits: int = 0

    @property
    def cache_hit_rate(self) -> float:
        """On-chip hit rate over the reads that probed the cache."""
        return self.cache_hits / max(self.cache_lookups, 1)

    @property
    def runtime_s(self) -> float:
        return self.runtime_ns * 1e-9

    @property
    def runtime_ms(self) -> float:
        return self.runtime_ns * 1e-6

    @property
    def reps(self) -> float:
        """Read edges per second = m * iterations / runtime (the paper's
        renamed REPS; the originals call it TEPS)."""
        if self.runtime_ns <= 0:
            return 0.0
        return self.edges * self.iterations / (self.runtime_ns * 1e-9)

    @property
    def teps(self) -> float:
        """Graph500 TEPS: m / runtime."""
        if self.runtime_ns <= 0:
            return 0.0
        return self.edges / (self.runtime_ns * 1e-9)
