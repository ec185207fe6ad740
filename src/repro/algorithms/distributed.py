"""Distributed edge-centric engine: HitGraph's architecture mapped onto
a TPU mesh (DESIGN.md §2/§5).

HitGraph on FPGA: partitions by source interval, PEs scatter updates
through a p×p crossbar into per-partition queues, gather applies them.
On a mesh: each ``data``-shard owns a vertex interval (its values) and
the edges whose *source* lies in that interval; scatter computes, per
destination shard, a segment-min of candidate values (the dst-sorted
update merging); the crossbar is a ``jax.lax.all_to_all``; gather is an
elementwise min against the local values.  The iteration is synchronous,
exactly like HitGraph's two-phase execution — the same semantics as
``algorithms/edge_centric.py`` (tests assert equality).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.algorithms.common import INF32
from repro.graphs.formats import Graph


def shard_edges(g: Graph, n_shards: int, weighted: bool = False):
    """Partition edges by source interval and pad shards to equal size.

    Returns (src, dst, w, valid) each of shape (n_shards, max_edges) and
    the padded interval size q.
    """
    q = -(-g.n // n_shards)                  # ceil
    part = g.src // q
    counts = np.bincount(part, minlength=n_shards)
    E = max(int(counts.max()), 1)
    src = np.zeros((n_shards, E), np.int32)
    dst = np.zeros((n_shards, E), np.int32)
    w = np.ones((n_shards, E), np.int32)
    valid = np.zeros((n_shards, E), bool)
    weights = (g.weights if g.weights is not None
               else np.ones(g.m, dtype=np.int32)).astype(np.int32)
    for s in range(n_shards):
        idx = np.nonzero(part == s)[0]
        src[s, :len(idx)] = g.src[idx]
        dst[s, :len(idx)] = g.dst[idx]
        w[s, :len(idx)] = weights[idx]
        valid[s, :len(idx)] = True
    return src, dst, w, valid, q


def make_min_step(mesh: Mesh, n_shards: int, q: int, add_weight: bool):
    """Build the jitted distributed scatter/crossbar/gather step."""

    def local_step(values_l, src_l, dst_l, w_l, valid_l):
        # values_l: (1, q) this shard's interval; edges: (1, E)
        values_l = values_l[0]
        src_l, dst_l, w_l, valid_l = (src_l[0], dst_l[0], w_l[0],
                                      valid_l[0])
        shard_id = jax.lax.axis_index("data")
        local_src = src_l - shard_id * q
        cand = values_l[local_src] + (w_l if add_weight else 0)
        cand = jnp.where(valid_l, cand, INF32)
        # scatter + merge: segment-min keyed by global dst slot, laid
        # out as (dst_shard, dst_local) -> the update "queues"
        seg = dst_l                                    # global id < S*q
        upd = jax.ops.segment_min(cand, seg, num_segments=n_shards * q)
        upd = upd.reshape(n_shards, q)
        # the crossbar: route each dst shard its queue
        recv = jax.lax.all_to_all(upd[:, None], "data", split_axis=0,
                                  concat_axis=1, tiled=False)
        # recv: (1, n_shards, q) partials destined for THIS shard
        gathered = recv.min(axis=1)[0]                 # (q,)
        new_vals = jnp.minimum(values_l, gathered)
        return new_vals[None], (new_vals != values_l).any()[None]

    stepped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("data", None), P("data", None), P("data", None),
                  P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data")),
        check_vma=False,
    )
    return jax.jit(stepped)


def run_wcc(g: Graph, mesh: Optional[Mesh] = None,
            max_iters: int = 10_000) -> np.ndarray:
    """Distributed WCC (min-label propagation); returns labels."""
    if mesh is None:
        n_dev = len(jax.devices())
        mesh = jax.make_mesh((n_dev,), ("data",))
    n_shards = mesh.shape["data"]
    src, dst, w, valid, q = shard_edges(g, n_shards)
    step = make_min_step(mesh, n_shards, q, add_weight=False)
    values = jnp.arange(n_shards * q, dtype=jnp.int32).reshape(
        n_shards, q)
    values = jnp.where(values < g.n, values, INF32)
    sh = NamedSharding(mesh, P("data", None))
    values = jax.device_put(values, sh)
    args = [jax.device_put(jnp.asarray(a), sh)
            for a in (src, dst, w, valid)]
    for _ in range(max_iters):
        values, changed = step(values, *args)
        if not bool(np.asarray(changed).any()):
            break
    return np.asarray(values).reshape(-1)[:g.n]


def run_sssp(g: Graph, root: int = 0, mesh: Optional[Mesh] = None,
             max_iters: int = 10_000) -> np.ndarray:
    if mesh is None:
        n_dev = len(jax.devices())
        mesh = jax.make_mesh((n_dev,), ("data",))
    n_shards = mesh.shape["data"]
    gw = g.with_unit_weights() if g.weights is None else g
    src, dst, w, valid, q = shard_edges(gw, n_shards, weighted=True)
    step = make_min_step(mesh, n_shards, q, add_weight=True)
    values = jnp.full((n_shards, q), INF32, jnp.int32)
    values = values.at[root // q, root % q].set(0)
    sh = NamedSharding(mesh, P("data", None))
    values = jax.device_put(values, sh)
    args = [jax.device_put(jnp.asarray(a), sh)
            for a in (src, dst, w, valid)]
    for _ in range(max_iters):
        values, changed = step(values, *args)
        if not bool(np.asarray(changed).any()):
            break
    return np.asarray(values).reshape(-1)[:g.n]
