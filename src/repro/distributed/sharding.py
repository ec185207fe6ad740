"""Parameter / activation sharding rules for the production mesh.

Strategy (DESIGN.md §5): FSDP x TP x pod-DP.

* every >= 2-D parameter is sharded on two axes where divisibility
  allows: its "model" dimension over the ``model`` axis and a second
  dimension over ``data`` (ZeRO-3); optimizer moments inherit the rule;
* activations: batch over (pod, data); heads / ffn / vocab over model —
  with per-arch fallbacks when a dimension is not divisible (e.g. Hymba's
  25 heads, whisper-tiny's 6);
* decode KV caches: batch over data, sequence over model
  (flash-decoding layout).

Rules are *structural*: they pattern-match parameter names produced by
``models/model.py`` and check divisibility against the concrete mesh, so
a new architecture gets sensible shardings with no per-arch table.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import vectorized as vec
from repro.distributed import context as dctx
from repro.models.config import ModelConfig

# name-suffix -> (model-parallel dim, fsdp dim); dims count from the end
# so stacked [L, ...] layers match too.
_MATRIX_RULES = {
    "wq": (-1, -2), "wk": (-1, -2), "wv": (-1, -2), "wo": (-2, -1),
    "w1": (-1, -2), "w3": (-1, -2), "w2": (-2, -1),
    "in_proj": (-1, -2), "out_proj": (-2, -1), "x_bc": (-2, -1),
    "r_rec": (-1, -2), "w_in": (-1, -2), "w_if": (-2, -1),
    "router": (None, -2), "img_adapter": (-1, -2),
    "lm_head": (-1, -2),
}


def _divisible(shape, dim, size) -> bool:
    return shape[dim] % size == 0 and shape[dim] >= size


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               mesh: Mesh, multi_pod: bool) -> P:
    """Sharding spec for one parameter."""
    name = path[-1]
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]
    spec = [None] * len(shape)
    if name == "embed":
        if _divisible(shape, 0, n_model):
            spec[0] = "model"
        if _divisible(shape, 1, n_data):
            spec[1] = "data"
        return P(*spec)
    rule = _MATRIX_RULES.get(name)
    if rule is None or len(shape) < 2:
        return P()                      # norms/scales: replicated
    tp_dim, fsdp_dim = rule
    # expert tensors (E, D, F): model axis shards experts (dim -3)
    if (name in ("w1", "w2", "w3") and len(shape) >= 3
            and len(path) >= 2 and path[-2] == "moe"):
        e_dim = len(shape) - 3
        if shape[e_dim] % n_model == 0:
            spec[e_dim] = "model"
        f_dim = len(shape) + (-2 if name == "w2" else -1)
        # hierarchical FSDP: shard the F dim over *data* only and
        # replicate across pods, so per-layer weight gathers stay on
        # intra-pod ICI; only the gradient reduction crosses the pod/DCI
        # axis (EXPERIMENTS §Perf hillclimb B).
        if shape[f_dim] % n_data == 0:
            spec[f_dim] = "data"
        return P(*spec)
    if tp_dim is not None and _divisible(shape, tp_dim, n_model):
        spec[tp_dim] = "model"
    if fsdp_dim is not None and _divisible(shape, fsdp_dim, n_data):
        spec[fsdp_dim] = "data"
    return P(*spec)


def serve_param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
                     mesh: Mesh) -> P:
    """Serving layout: weights stay TP-resident (model axis only, no
    FSDP) — decode must not all-gather weights every layer.  At bf16 a
    35B model is ~4 GiB/chip at TP=16 (EXPERIMENTS §Perf hillclimb C)."""
    name = path[-1]
    n_model = mesh.shape["model"]
    spec = [None] * len(shape)
    if name == "embed":
        if _divisible(shape, 0, n_model):
            spec[0] = "model"
        return P(*spec)
    rule = _MATRIX_RULES.get(name)
    if rule is None or len(shape) < 2:
        return P()
    if (name in ("w1", "w2", "w3") and len(shape) >= 3
            and len(path) >= 2 and path[-2] == "moe"):
        e_dim = len(shape) - 3
        if shape[e_dim] % n_model == 0:
            spec[e_dim] = "model"
        return P(*spec)
    tp_dim, _ = rule
    if tp_dim is not None and _divisible(shape, tp_dim, n_model):
        spec[tp_dim] = "model"
    return P(*spec)


def tree_shardings(params_shape, mesh: Mesh, multi_pod: bool,
                   serve: bool = False):
    """NamedShardings for a (shape-)pytree of parameters."""

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, path + (str(i),))
                         for i, v in enumerate(tree))
        shape = tree.shape
        spec = (serve_param_spec(path, shape, mesh) if serve
                else param_spec(path, shape, mesh, multi_pod))
        return NamedSharding(mesh, spec)

    return walk(params_shape, ())


def activation_rules(cfg: ModelConfig, mesh: Mesh,
                     multi_pod: bool) -> Dict[str, P]:
    """Per-arch activation rules with divisibility fallbacks."""
    batch = ("pod", "data") if multi_pod else ("data",)
    n_model = mesh.shape["model"]
    rules: Dict[str, P] = {"tokens": P(batch, None),
                           "act_btd": P(batch, None, None)}
    if cfg.d_ff and cfg.d_ff % n_model == 0:
        rules["act_btf"] = P(batch, None, "model")
    if cfg.n_heads % n_model == 0:
        rules["act_heads"] = P(batch, None, "model", None)
    else:
        # indivisible head counts (arctic 56, hymba 25, gemma 8, whisper
        # 6): REPLICATE q/k/v over the model axis.  Any partial layout
        # (head_dim- or sequence-sharded) makes GSPMD move *score-sized*
        # (B,h,S,S) tensors every attention chunk — measured 1.1 TB per
        # scan region on arctic train_4k (EXPERIMENTS §Perf hillclimb B:
        # B2 refuted, B3 adopted).  Cost: attention compute is redundant
        # across model ranks (~13% extra total FLOPs on arctic).
        rules["act_heads"] = P(batch, None, None, None)
    # k/v carry n_kv_heads, which is often < model-axis size (GQA).
    # When kv heads don't divide the model axis, REPLICATE k/v (the
    # standard GQA-TP choice): sharding them on head_dim instead makes
    # every attention contraction a partial sum and - measured on
    # qwen3-0.6b train_4k - injects ~1.2 TB/step of per-chunk
    # collective-permutes inside the attention scan (EXPERIMENTS §Perf).
    if cfg.n_kv_heads % n_model == 0 and cfg.n_heads % n_model == 0:
        rules["act_kv_heads"] = P(batch, None, "model", None)
    else:
        rules["act_kv_heads"] = P(batch, None, None, None)
    rules["replicated2d"] = P(None, None)
    if cfg.vocab % n_model == 0:
        rules["logits"] = P(batch, None, "model")
    if cfg.family == "ssm":
        di = cfg.d_model * max(cfg.ssm_expand, 1)
        dh = di // cfg.n_heads
        if dh % n_model == 0:
            rules["act_ssm_heads"] = P(batch, None, None, "model")
    return rules


def make_ctx(cfg: ModelConfig, mesh: Mesh, multi_pod: bool) -> dctx.ShardCtx:
    return dctx.ShardCtx(
        mesh=mesh,
        rules=activation_rules(cfg, mesh, multi_pod),
        token_axes=("pod", "data") if multi_pod else ("data",),
        expert_axis="model",
    )


# ---------------------------------------------------------------------------
# case-sharded sweep serving
# ---------------------------------------------------------------------------
#
# The batched fused scan (``vec.fused_scan_batch``) vmaps independent
# cases down one device.  On an N-device host the case batch shards over
# a 1-D ``("cases",)`` mesh (``launch.mesh.make_sweep_mesh``) instead:
# every device serves its slice of the batch with the SAME per-case math
# (no cross-device collectives — the scans are independent), so the
# result is bit-identical to the unsharded vmap for any device count.
# The batch pads up to a multiple of the mesh size with replicas of case
# 0 (discarded after); padding with *real* work keeps every device on
# the identical compiled scan shape.


def _pad_cases(arr, pad):
    if not pad:
        return jnp.asarray(arr)
    arr = jnp.asarray(arr)
    reps = jnp.broadcast_to(arr[:1], (pad,) + arr.shape[1:])
    return jnp.concatenate([arr, reps], axis=0)


def _sweep_state(M, C, n_banks, banks_per_rank):
    single = vec.init_lean_carry(C, n_banks, banks_per_rank)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (M,) + x.shape),
        single + (jnp.zeros((C,), dtype=jnp.int32),))


def sharded_fused_scan_batch(issue, meta, boundary, timing, n_banks,
                             banks_per_rank, mesh: Mesh,
                             as_numpy=True):
    """Case-sharded :func:`repro.core.vectorized.fused_scan_batch`:
    leading axis = case batch, sharded over ``mesh``'s ``cases`` axis.
    Bit-identical rows for any device count."""
    M, S, C, K = issue.shape
    D = mesh.shape["cases"]
    pad = (-M) % D
    issue, meta, boundary = (_pad_cases(issue, pad),
                             _pad_cases(meta, pad),
                             _pad_cases(boundary, pad))
    timing = _pad_cases(jnp.asarray(timing, jnp.int32), pad)
    state = _sweep_state(M + pad, C, n_banks, banks_per_rank)
    # check_vma=False: every operand is case-sharded; there is no
    # replicated output for the checker to reason about
    fn = jax.shard_map(vec._fused_scan_batch, mesh=mesh,
                       in_specs=P("cases"), out_specs=P("cases"),
                       check_vma=False)
    fins = []
    pos = 0
    for size in vec.plan_chunks(S):
        vec.count_dispatch("fused_batch")
        fin, state = fn(issue[:, pos:pos + size],
                        meta[:, pos:pos + size],
                        boundary[:, pos:pos + size], timing, state)
        fins.append(fin)
        pos += size
    fin = (fins[0] if len(fins) == 1
           else jnp.concatenate(fins, axis=1))[:M]
    state = jax.tree.map(lambda x: x[:M], state[:5])
    return (np.asarray(fin) if as_numpy else fin), state


def sharded_fused_scan_batch_shared(issue, meta, boundary, timing,
                                    n_banks, banks_per_rank, mesh: Mesh,
                                    as_numpy=True):
    """Case-sharded shared-stream variant: ONE packed program (streams
    replicated on every device) served against a sharded batch of
    timing vectors — the sharded twin of
    :func:`repro.core.vectorized.fused_scan_batch_shared`."""
    M = timing.shape[0]
    S, C, K = issue.shape
    D = mesh.shape["cases"]
    pad = (-M) % D
    issue = jnp.asarray(issue)
    meta = jnp.asarray(meta)
    boundary = jnp.asarray(boundary)
    timing = _pad_cases(jnp.asarray(timing, jnp.int32), pad)
    state = _sweep_state(M + pad, C, n_banks, banks_per_rank)
    fn = jax.shard_map(vec._fused_scan_batch_shared, mesh=mesh,
                       in_specs=(P(), P(), P(), P("cases"), P("cases")),
                       out_specs=P("cases"), check_vma=False)
    fins = []
    pos = 0
    for size in vec.plan_chunks(S):
        vec.count_dispatch("fused_batch")
        fin, state = fn(issue[pos:pos + size], meta[pos:pos + size],
                        boundary[pos:pos + size], timing, state)
        fins.append(fin)
        pos += size
    fin = (fins[0] if len(fins) == 1
           else jnp.concatenate(fins, axis=1))[:M]
    state = jax.tree.map(lambda x: x[:M], state[:5])
    return (np.asarray(fin) if as_numpy else fin), state


# ---------------------------------------------------------------------------
# batch / cache shardings
# ---------------------------------------------------------------------------

def batch_shardings(batch_shape, mesh: Mesh, multi_pod: bool):
    batch_axes = ("pod", "data") if multi_pod else ("data",)

    def one(x):
        spec = [None] * len(x.shape)
        n = int(np.prod([mesh.shape[a] for a in batch_axes]))
        if len(x.shape) >= 1 and x.shape[0] % n == 0:
            spec[0] = batch_axes if multi_pod else "data"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, batch_shape)


def cache_shardings(cache_shape, mesh: Mesh, multi_pod: bool,
                    cfg: ModelConfig):
    """Decode-cache shardings: batch -> data, KV sequence -> model."""
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    n_batch = int(np.prod([mesh.shape[a] for a in batch_axes]))
    n_model = mesh.shape["model"]
    ba = batch_axes if multi_pod else "data"

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, tuple):
            return tuple(walk(v, path + (str(i),))
                         for i, v in enumerate(tree))
        shape = tree.shape
        spec = [None] * len(shape)
        name = path[-1]
        # stacked per-layer caches: dim0 = layer (xLSTM m-states carry
        # two stack dims: (groups, group_size-1, ...))
        off = (2 if path and path[0] == "m"
               else 1 if path and path[0] in ("layers", "s") else 0)
        if name in ("k", "v") and len(shape) >= off + 4:
            if shape[off + 0] % n_batch == 0:
                spec[off + 0] = ba
            if shape[off + 1] % n_model == 0:
                spec[off + 1] = "model"          # sequence-sharded KV
        elif name in ("0", "1") and "cross_kv" in path:
            if shape[off + 0] % n_batch == 0:
                spec[off + 0] = ba
        elif len(shape) >= off + 2 and name not in ("pos_slots", "length",
                                                    "pos"):
            if shape[off + 0] % n_batch == 0:
                spec[off + 0] = ba
            # shard the widest remaining dim over model if divisible
            dims = list(range(off + 1, len(shape)))
            if dims:
                widest = max(dims, key=lambda i: shape[i])
                if shape[widest] % n_model == 0:
                    spec[widest] = "model"
        return NamedSharding(mesh, P(*spec))

    return walk(cache_shape, ())
