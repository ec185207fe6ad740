"""Plain DRAM reference: address decode and in-order request service.

Each channel serves its requests one at a time in program order, with
the per-bank open row, the per-rank activate limits (tRRD, and tFAW over
the fourth-last activate) and a shared data bus.  Phases are separated
by barriers: phase ``p`` starts when every request of phase ``p - 1``
has finished, and its issue cycles count from that start.  This is the
service model the configuration states, written request by request in
``jax.numpy`` (``lax.scan`` over one channel's stream, ``vmap`` over
channels and over timing vectors) so that it runs in seconds at full
size.  It shares no code with the program's blocked serve.

``control=True`` is the control: the same service with the phase
barrier kept per channel (each channel starts the next phase when its
own requests of this phase have finished), a guarantee the
configuration states and the shortcut a faster serve would be tempted
to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TIMING_FIELDS = ("tCL", "tRCD", "tRP", "tRAS", "tBL", "tRRD", "tFAW")
LINE_BYTES = 64
NEG = -(1 << 30)
#: steps per dispatch of the reference scan (one compiled shape)
CHUNK = 1 << 13


class Device:
    """Geometry and address mapping of one memory configuration, read
    from the ``memory`` block of a configuration file."""

    def __init__(self, mem: dict):
        self.channels = int(mem["channels"])
        self.ranks = int(mem["ranks"])
        self.banks = int(mem["banks"])
        self.rows = int(mem["rows"])
        self.row_bytes = int(mem["row_bytes"])
        self.clock_ghz = float(mem["clock_ghz"])
        self.order = tuple(mem["order"])
        self.banks_per_channel = self.ranks * self.banks
        self.capacity_bytes = (self.channels * self.ranks * self.banks
                               * self.rows * self.row_bytes)
        self.sizes = {"channel": self.channels,
                      "column": self.row_bytes // LINE_BYTES,
                      "rank": self.ranks, "bank": self.banks,
                      "row": self.rows}
        for comp, size in self.sizes.items():
            if size & (size - 1):
                raise ValueError(f"{comp} count {size} is not a power of 2")

    def decode(self, lines: np.ndarray):
        """``(channel, bank_in_channel, row)`` of each line address;
        components are taken LSB first in ``order``, and bits above the
        device's capacity are ignored."""
        rem = np.asarray(lines, dtype=np.int64)
        comps = {}
        for comp in self.order:
            size = self.sizes[comp]
            comps[comp] = rem & (size - 1)
            rem = rem >> (size.bit_length() - 1)
        return (comps["channel"], comps["rank"] * self.banks + comps["bank"],
                comps["row"])


def timing_vector(t: dict) -> np.ndarray:
    return np.array([int(t[f]) for f in TIMING_FIELDS], dtype=np.int32)


def _init_state(B: int, R: int):
    return (jnp.full((B,), -1, jnp.int32),        # open row
            jnp.full((B,), NEG, jnp.int32),       # last activate
            jnp.zeros((B,), jnp.int32),           # next column command
            jnp.zeros((), jnp.int32),             # data bus free
            jnp.full((R, 4), NEG, jnp.int32),     # last four activates
            jnp.zeros((R,), jnp.int32),           # their ring pointer
            jnp.full((R,), NEG, jnp.int32))       # last activate per rank


def _serve_one(state, x, t, bpr):
    """Serve one request on one channel.  Per-bank and per-rank state
    is selected and updated through one-hot masks over the few banks and
    ranks (dense ops, which XLA runs faster than scatters in a loop)."""
    open_row, act_t, avail, bus, hist, ptr, last = state
    iss, b, r, v = x
    tCL, tRCD, tRP, tRAS, tBL, tRRD, tFAW = t
    B, R = open_row.shape[0], ptr.shape[0]
    ohb = jnp.arange(B, dtype=jnp.int32) == b
    ohr = jnp.arange(R, dtype=jnp.int32) == b // bpr

    def pick(a, oh):
        return jnp.max(jnp.where(oh, a, NEG))

    o = jnp.max(jnp.where(ohb, open_row, -2))
    hit = o == r
    empty = o == -1
    p_r = jnp.max(jnp.where(ohr, ptr, 0))
    ohp = jnp.arange(4, dtype=jnp.int32) == p_r
    h_r = jnp.max(jnp.where(ohr[:, None], hist, NEG), axis=0)
    floor = jnp.maximum(pick(last, ohr) + tRRD, pick(h_r, ohp) + tFAW)
    ready = jnp.maximum(iss, pick(avail, ohb))
    act = jnp.where(
        empty, jnp.maximum(ready, floor),
        jnp.maximum(jnp.maximum(ready, pick(act_t, ohb) + tRAS) + tRP,
                    floor))
    col = jnp.where(hit, ready, act + tRCD)
    fin = jnp.maximum(col + tCL, bus) + tBL
    opens = v & ~hit
    ob, orr = ohb & opens, ohr & opens
    new = (jnp.where(ob, r, open_row),
           jnp.where(ob, act, act_t),
           jnp.where(ohb & v, col + tBL, avail),
           jnp.where(v, fin, bus),
           jnp.where(orr[:, None] & ohp, act, hist),
           jnp.where(orr, (p_r + 1) % 4, ptr),
           jnp.where(orr, act, last))
    out = (jnp.where(v, fin, NEG), v & hit, v & ~hit & ~empty)
    return new, out


@functools.partial(jax.jit, static_argnames=("bpr",))
def _serve_chunk(state, start, timing, issue, bank, row, valid, *, bpr):
    """One chunk of every channel's stream for every timing vector:
    ``state`` leaves are ``[M, C, ...]``, ``start`` ``[M, C]`` (the
    cycle each channel's phase starts at), ``timing`` ``[M, 7]``, the
    streams ``[C, L]``.  Returns the new state, the latest finish per
    timing vector and channel, and the row hits and conflicts."""

    def one_timing(st, t0, tm):
        t = tuple(tm[i] for i in range(len(TIMING_FIELDS)))

        def one_channel(s, c0, iss, b, r, v):
            def step(c, x):
                return _serve_one(c, x, t, bpr)
            return jax.lax.scan(step, s, (iss + c0, b, r, v))

        st, (fin, hit, confl) = jax.vmap(one_channel)(
            st, t0, issue, bank, row, valid)
        return st, fin.max(axis=1), hit.sum(), confl.sum()

    return jax.vmap(one_timing)(state, start, timing)


def serve_program(dev: Device, lines: np.ndarray, issue: np.ndarray,
                  offsets: np.ndarray, timings: np.ndarray,
                  control: bool = False):
    """Serve a whole multi-phase program against ``M`` timing vectors.

    ``lines``/``issue`` are in program order, ``issue`` phase-relative,
    ``offsets`` ``int[P + 1]``.  Returns ``(ends[M, P], hits[P],
    conflicts[P])``: the cycle at which each phase ends (it starts where
    the previous one ended, the first at 0) and the row-buffer hits and
    conflicts of each phase (timing does not change them)."""
    timings = np.asarray(timings, dtype=np.int32)
    M = timings.shape[0]
    C, B = dev.channels, dev.banks_per_channel
    ch, bank, row = dev.decode(lines)
    state = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (M, C) + x.shape),
        _init_state(B, dev.ranks))
    start = np.zeros((M, C), dtype=np.int64)
    P = len(offsets) - 1
    ends = np.zeros((M, P), dtype=np.int64)
    hits = np.zeros(P, dtype=np.int64)
    confl = np.zeros(P, dtype=np.int64)
    tm = jnp.asarray(timings)
    for p in range(P):
        s, e = int(offsets[p]), int(offsets[p + 1])
        c = ch[s:e]
        counts = np.bincount(c, minlength=C)
        L = -(-int(counts.max()) // CHUNK) * CHUNK
        slot = np.empty(e - s, dtype=np.int64)
        for k in range(C):
            idx = np.nonzero(c == k)[0]
            slot[idx] = np.arange(len(idx))
        streams = []
        for a in (issue[s:e], bank[s:e], row[s:e]):
            out = np.zeros((C, L), dtype=np.int32)
            out[c, slot] = a
            streams.append(out)
        valid = np.zeros((C, L), dtype=bool)
        valid[c, slot] = True
        if int(start.max()) + int(issue[s:e].max()) >= 2**31 - 2**26:
            raise ValueError("cycle counts leave the int32 range")
        t0 = jnp.asarray(start.astype(np.int32))
        parts = []
        for j in range(0, L, CHUNK):
            state, fmax, h, cf = _serve_chunk(
                state, t0, tm, *(jnp.asarray(a[:, j:j + CHUNK])
                                 for a in (*streams, valid)),
                bpr=dev.banks)
            parts.append((fmax, h, cf))
        fmax = np.stack([np.asarray(x[0]) for x in parts]).max(axis=0)
        fmax = np.maximum(fmax.astype(np.int64), start)
        hits[p] = sum(int(np.asarray(x[1])[0]) for x in parts)
        confl[p] = sum(int(np.asarray(x[2])[0]) for x in parts)
        ends[:, p] = fmax.max(axis=1)
        start = fmax if control else np.repeat(ends[:, p:p + 1], C, axis=1)
    return ends, hits, confl
