"""Plain reference of one HitGraph scenario: WCC, the request program of
the edge-centric accelerator, the stream prefetcher, and the DRAM
service of :mod:`reference.dram`.

HitGraph (Zhou et al., TPDS 2019; arXiv:2010.13619 Sect. 3.2): ``p``
source-interval partitions stored as destination-sorted edge lists,
partition ``k`` on channel ``k mod n_pes``.  Every iteration has a
scatter phase (value prefetch, edge reads paced at ``pipelines`` edges
per accelerator cycle, update writes into per-partition queues) and a
gather phase (value prefetch, queue reads, writes of changed values),
with update merging, update filtering and partition skipping.  This is
a copy, kept with the benchmark, of the request-program arithmetic the
program had when the benchmark was written; it imports nothing of the
program.
"""

from __future__ import annotations

import math

import numpy as np

from .dram import LINE_BYTES

INF = np.int64(2**31 - 2**24)


def wcc(n: int, src: np.ndarray, dst: np.ndarray):
    """Synchronous min-label propagation.  Returns the labels and, per
    iteration, ``(active_before, changed)``."""
    order = np.argsort(dst, kind="stable")
    src_s, dst_s = src[order], dst[order]
    starts = np.flatnonzero(np.diff(dst_s, prepend=np.int64(-1)))
    heads = dst_s[starts]
    values = np.arange(n, dtype=np.int64)
    active = np.ones(n, dtype=bool)
    iters = []
    while active.any():
        cand = np.where(active[src_s], values[src_s], INF)
        new = values.copy()
        if len(starts):
            new[heads] = np.minimum(values[heads],
                                    np.minimum.reduceat(cand, starts))
        changed = new != values
        iters.append((active, changed))
        values, active = new, changed
    return values, iters


def _ragged_arange(counts):
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, dtype=np.int64)
    return (np.arange(total, dtype=np.int64)
            - np.repeat(np.cumsum(counts) - counts, counts))


def _spans(byte_start, nbytes):
    """First line and line count of each byte range."""
    byte_start = np.asarray(byte_start, dtype=np.int64)
    nbytes = np.asarray(nbytes, dtype=np.int64)
    first = byte_start // LINE_BYTES
    last = (byte_start + np.maximum(nbytes, 1) - 1) // LINE_BYTES
    return first, np.where(nbytes > 0, last - first + 1, 0)


def _lines(first, counts):
    counts = np.asarray(counts, dtype=np.int64)
    return np.repeat(np.asarray(first, dtype=np.int64),
                     counts) + _ragged_arange(counts)


def _bulk(start, counts):
    return np.repeat(np.asarray(start, dtype=np.int64),
                     np.asarray(counts, dtype=np.int64))


def _paced(start, window, counts):
    """Element ``i`` of group ``g`` issues at ``start[g] + floor(i *
    window[g] / counts[g])``."""
    counts = np.asarray(counts, dtype=np.int64)
    i = _ragged_arange(counts).astype(np.float64)
    w = np.repeat(np.asarray(window, dtype=np.float64), counts)
    n = np.repeat(counts.astype(np.float64), counts)
    t = np.repeat(np.asarray(start, dtype=np.float64), counts)
    return (t + i * w / n).astype(np.int64)


def _merge(parts):
    """Concatenate per-PE streams, then order by issue cycle (ties keep
    the PE order)."""
    lines = np.concatenate([p[0] for p in parts])
    issue = np.concatenate([p[1] for p in parts])
    block = np.concatenate([p[2] for p in parts])
    wr = np.zeros(len(lines), dtype=bool)
    wr[len(parts[0][0]) + len(parts[1][0]):] = True
    order = np.argsort(block, kind="stable")
    order = order[np.argsort(issue[order], kind="stable")]
    return lines[order], wr[order], issue[order]


class HitGraphProgram:
    """The request program of one HitGraph design on one graph."""

    def __init__(self, n, src, dst, design: dict, dev, clock_ghz: float):
        self.n = n
        self.d = design
        q = int(design["partition_elements"])
        self.q = q
        starts = np.arange(0, max(n, 1), q, dtype=np.int64)
        ends = np.minimum(starts + q, n)
        self.p = p = len(starts)
        key = (src // q) * np.int64(n) + dst
        order = np.argsort(key, kind="stable")
        self.e_src = src[order]
        self.e_dst = dst[order]
        self.edge_key = key[order]
        e_spart = self.edge_key // n
        e_dpart = self.e_dst // q
        m_k = np.bincount(e_spart, minlength=p)
        in_counts = np.bincount(e_dpart, minlength=p)
        # per-channel arrays laid out back to back, line aligned
        cap = dev.capacity_bytes // dev.channels
        cursor = [c * cap for c in range(dev.channels)]
        val_base, edge_base, queue_base = [], [], []

        def alloc(c, nbytes):
            at = cursor[c]
            cursor[c] = at + -(-nbytes // LINE_BYTES) * LINE_BYTES
            return at

        for k in range(p):
            c = k % design["n_pes"]
            n_k = int(ends[k] - starts[k])
            val_base.append(alloc(c, n_k * design["value_bytes"]))
            edge_base.append(alloc(c, int(m_k[k]) * design["edge_bytes"]))
            qcap = int(min(in_counts[k], n_k * p)) + p
            queue_base.append(alloc(c, qcap * design["update_bytes"]))
        for c in range(dev.channels):
            if cursor[c] - c * cap > cap:
                raise ValueError("graph does not fit the channel capacity")
        self.start = starts
        self.val_base = np.asarray(val_base, dtype=np.int64)
        self.edge_base = np.asarray(edge_base, dtype=np.int64)
        self.queue_base = np.asarray(queue_base, dtype=np.int64)
        self.pre_first, self.pre_cnt = _spans(
            self.val_base, (ends - starts) * design["value_bytes"])
        self.edge_first, self.edge_cnt = _spans(
            self.edge_base, m_k * design["edge_bytes"])
        self.ratio = clock_ghz / design["acc_ghz"]
        self.win = (np.ceil(m_k / design["pipelines"])
                    * self.ratio).astype(np.int64)

    def _cursor(self, w):
        """Exclusive running start of each partition on its PE."""
        t0 = np.zeros(self.p, dtype=np.int64)
        for c in range(self.d["n_pes"]):
            sl = slice(c, None, self.d["n_pes"])
            t0[sl] = np.cumsum(w[sl]) - w[sl]
        return t0

    def _updates(self, active):
        keys = self.edge_key
        if self.d["update_filtering"]:
            keys = keys[active[self.e_src]]
        if self.d["update_merging"] and len(keys):
            keep = np.ones(len(keys), dtype=bool)
            keep[1:] = keys[1:] != keys[:-1]
            keys = keys[keep]
        return keys // self.n, keys % self.n

    def _scatter(self, active, u_count, q_off):
        p, d = self.p, self.d
        ub = d["update_bytes"]
        if d["partition_skipping"]:
            proc = np.logical_or.reduceat(active, self.start)
        else:
            proc = np.ones(p, dtype=bool)
        t0 = self._cursor(np.where(proc, np.maximum(self.win, 1), 0))
        blk = p + 2
        pk = np.nonzero(proc)[0]
        pre = (_lines(self.pre_first[pk], self.pre_cnt[pk]),
               _bulk(t0[pk], self.pre_cnt[pk]),
               np.repeat(pk * blk, self.pre_cnt[pk]))
        edges = (_lines(self.edge_first[pk], self.edge_cnt[pk]),
                 _paced(t0[pk], self.win[pk], self.edge_cnt[pk]),
                 np.repeat(pk * blk + 1, self.edge_cnt[pk]))
        kk, jj = np.nonzero(u_count)
        sel = proc[kk]
        kk, jj = kk[sel], jj[sel]
        first, cnt = _spans(self.queue_base[jj] + q_off[kk, jj] * ub,
                            u_count[kk, jj] * ub)
        writes = (_lines(first, cnt), _paced(t0[kk], self.win[kk], cnt),
                  np.repeat(kk * blk + 2 + jj, cnt))
        return _merge([pre, edges, writes])

    def _gather(self, changed, dsts, dpart, u_count):
        p, d = self.p, self.d
        ub, vb = d["update_bytes"], d["value_bytes"]
        U = u_count.sum(axis=0)
        proc = (U > 0) if d["partition_skipping"] else np.ones(p, bool)
        win = (np.ceil(U / d["pipelines"]) * self.ratio).astype(np.int64)
        t0 = self._cursor(np.where(proc, np.maximum(win, 1), 0))
        jk = np.nonzero(proc)[0]
        pre = (_lines(self.pre_first[jk], self.pre_cnt[jk]),
               _bulk(t0[jk], self.pre_cnt[jk]),
               np.repeat(jk * 3, self.pre_cnt[jk]))
        q_first, q_cnt = _spans(self.queue_base, U * ub)
        reads = (_lines(q_first[jk], q_cnt[jk]),
                 _paced(t0[jk], win[jk], q_cnt[jk]),
                 np.repeat(jk * 3 + 1, q_cnt[jk]))
        sel = changed[dsts]
        jd, dd = dpart[sel], dsts[sel]
        line = (self.val_base[jd] + (dd - self.start[jd]) * vb) // LINE_BYTES
        order = np.lexsort((line, jd))
        jd, line = jd[order], line[order]
        if len(jd):
            keep = np.ones(len(jd), dtype=bool)
            keep[1:] = (jd[1:] != jd[:-1]) | (line[1:] != line[:-1])
            jd, line = jd[keep], line[keep]
        w_cnt = np.bincount(jd, minlength=p)
        jp = np.nonzero(w_cnt)[0]
        writes = (line, _paced(t0[jp], win[jp], w_cnt[jp]),
                  np.repeat(jp * 3 + 2, w_cnt[jp]))
        return _merge([pre, reads, writes])

    def phases(self, iters):
        """``[(name, lines, is_write, issue), ...]`` for the whole run,
        empty phases left out."""
        p = self.p
        out = []
        for it, (active, changed) in enumerate(iters):
            kp, dsts = self._updates(active)
            dpart = dsts // self.q
            u_count = np.bincount(kp * p + dpart,
                                  minlength=p * p).reshape(p, p)
            q_off = np.zeros((p, p), dtype=np.int64)
            q_off[1:] = np.cumsum(u_count, axis=0)[:-1]
            out.append((f"it{it}_scatter",
                        *self._scatter(active, u_count, q_off)))
            out.append((f"it{it}_gather",
                        *self._gather(changed, dsts, dpart, u_count)))
        return [ph for ph in out if len(ph[1])]


def prefetch(lines, is_write, issue, degree: int):
    """Sequential stream buffer of ``degree`` requests: in a run of
    reads to consecutive lines, read ``i`` may issue as early as read
    ``max(i - degree, run head)``.  Returns the new issue cycles and the
    number of reads covered by a run."""
    r = np.nonzero(~is_write)[0]
    if len(r) == 0 or degree <= 0:
        return issue, 0
    ln = lines[r]
    head_mask = np.ones(len(r), dtype=bool)
    head_mask[1:] = ln[1:] != ln[:-1] + 1
    head = np.nonzero(head_mask)[0][np.cumsum(head_mask) - 1]
    idx = np.arange(len(r), dtype=np.int64)
    out = issue.copy()
    out[r] = np.minimum(issue[r], issue[r[np.maximum(idx - degree, head)]])
    return out, int((idx > head).sum())


def partition_elements(n: int, design: dict) -> int:
    """A design names its partitions by count or by size."""
    if design.get("partitions") is not None:
        return max(math.ceil(n / int(design["partitions"])), 1)
    return int(design["partition_elements"])


def run(graph: dict, config: dict, scenarios, control: bool = False):
    """Reference reports of ``scenarios`` (the benchmark's scenario
    dicts) on ``graph`` (``n``, ``src``, ``dst``, ``name``).  Scenarios
    of one design share its request program and are served against all
    their timing vectors at once; ``control`` selects the control of
    :func:`reference.dram.serve_program`.  Returns ``(labels, {key:
    report})``."""
    from .dram import Device, serve_program, timing_vector
    if config["problem"] != "wcc":
        raise ValueError("the HitGraph reference runs WCC only")
    n, src, dst = graph["n"], graph["src"], graph["dst"]
    labels, iters = wcc(n, src, dst)
    groups = {}
    for sc in scenarios:
        groups.setdefault(sc["design_key"], []).append(sc)
    out = {}
    for scs in groups.values():
        dev = Device(scs[0]["memory"])
        design = dict(scs[0]["design"])
        design["partition_elements"] = partition_elements(n, design)
        cache = scs[0]["cache"] or {}
        if cache.get("lines", 0):
            raise ValueError("the HitGraph reference has no vertex cache")
        prog = HitGraphProgram(n, src, dst, design, dev, dev.clock_ghz)
        names, lines, issue, counts = [], [], [], []
        covered = 0
        for name, ln, wr, iss in prog.phases(iters):
            iss, c = prefetch(ln, wr, iss, cache.get("prefetch_degree", 0))
            covered += c
            names.append(name)
            lines.append(ln)
            issue.append(iss)
            counts.append(len(ln))
        offsets = np.concatenate([[0], np.cumsum(counts)])
        ends, hits, confl = serve_program(
            dev, np.concatenate(lines), np.concatenate(issue), offsets,
            np.stack([timing_vector(sc["memory"]["timing"]) for sc in scs]),
            control=control)
        total = int(offsets[-1])
        for m, sc in enumerate(scs):
            starts = np.concatenate([[0], ends[m, :-1]])
            out[sc["key"]] = {
                "system": "hitgraph", "problem": "wcc",
                "runtime_ns": int(ends[m, -1]) / dev.clock_ghz,
                "iterations": len(iters), "edges": len(src), "vertices": n,
                "total_requests": total, "total_bytes": total * LINE_BYTES,
                "row_hit_rate": int(hits.sum()) / max(total, 1),
                "cache_lookups": 0, "cache_hits": 0,
                "prefetch_hits": covered,
                "phases": [
                    [names[p], int(counts[p]), int(counts[p]) * LINE_BYTES,
                     int(starts[p]), int(ends[m, p]), int(hits[p]),
                     int(confl[p])]
                    for p in range(len(names))],
            }
    return labels, out
