"""Transformer building blocks: norms, RoPE, GQA attention (train /
prefill / chunked-long / decode), MLPs, and the MoE FFN (reference dense
dispatch + the production shard_map EP path with FSDP weight gathering
and explicit all-to-all).

All functions are pure; parameters are nested dicts of arrays.  Layers
consult ``distributed.context`` for sharding hints so the identical code
traces for single-CPU smoke tests and the 512-chip dry-run.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import context as dctx
from repro.models.config import ModelConfig

Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# initializers / numerics
# ---------------------------------------------------------------------------

def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(max(in_axis_size, 1))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rms_norm(x, weight, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * weight).astype(dt)


def rope(x, positions, theta):
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-np.arange(0, half) * 2.0 / dh)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": _dense_init(ks[0], (d, cfg.n_heads * hd), d, dtype),
        "wk": _dense_init(ks[1], (d, cfg.n_kv_heads * hd), d, dtype),
        "wv": _dense_init(ks[2], (d, cfg.n_kv_heads * hd), d, dtype),
        "wo": _dense_init(ks[3], (cfg.n_heads * hd, d),
                          cfg.n_heads * hd, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _qkv(x, p, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd = cfg.hd
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = dctx.constrain(q, "act_heads")
    k = dctx.constrain(k, "act_kv_heads")
    v = dctx.constrain(v, "act_kv_heads")
    return q, k, v


def _sdpa(q, k, v, cfg: ModelConfig, *, causal, q_pos0=0, k_pos0=0,
          window=0, k_len=None):
    """q: (B,Sq,H,Dh); k,v: (B,Sk,Hkv,Dh).  Grouped-query attention with
    optional causal / sliding-window masking and a valid-length bound."""
    B, Sq, H, Dh = q.shape
    Sk = k.shape[1]
    G = cfg.q_per_kv
    qg = q.reshape(B, Sq, cfg.n_kv_heads, G, Dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k) / math.sqrt(Dh)
    scores = scores.astype(jnp.float32)
    qi = q_pos0 + jnp.arange(Sq)[:, None]
    ki = k_pos0 + jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= qi >= ki
    if window:
        mask &= ki > qi - window
    if k_len is not None:
        mask &= ki < k_len
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, Sq, H * Dh)


def _sdpa_chunked(q, k, v, cfg: ModelConfig, *, window=0):
    """Memory-bounded causal attention for long prefill: outer scan over
    query chunks, inner online-softmax scan over KV chunks — attention
    scores never materialize beyond (B, Hkv, G, cq, ck)."""
    B, S, H, Dh = q.shape
    G = cfg.q_per_kv
    c = cfg.attn_chunk
    assert S % c == 0, (S, c)
    nq = S // c
    qg = q.reshape(B, nq, c, cfg.n_kv_heads, G, Dh)
    kc = k.reshape(B, nq, c, cfg.n_kv_heads, Dh)
    vc = v.reshape(B, nq, c, cfg.n_kv_heads, Dh)
    scale = 1.0 / math.sqrt(Dh)

    def q_block(qi, q_blk):
        # online softmax over kv blocks 0..qi
        m0 = jnp.full((B, cfg.n_kv_heads, G, c), -1e30, jnp.float32)
        l0 = jnp.zeros((B, cfg.n_kv_heads, G, c), jnp.float32)
        acc0 = jnp.zeros((B, c, cfg.n_kv_heads, G, Dh), jnp.float32)

        def kv_block(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            s = jnp.einsum("bskgd,btkd->bkgst", q_blk, k_blk) * scale
            s = s.astype(jnp.float32)
            qpos = qi * c + jnp.arange(c)[:, None]
            kpos = ki * c + jnp.arange(c)[None, :]
            msk = qpos >= kpos
            if window:
                msk &= kpos > qpos - window
            s = jnp.where(msk, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = (acc * corr.transpose(0, 3, 1, 2)[..., None]
                       + jnp.einsum("bkgst,btkd->bskgd",
                                    p.astype(q_blk.dtype), v_blk))
            return (m_new, l_new, acc_new), None

        ks_idx = jnp.arange(nq)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, acc0), (ks_idx, kc.swapaxes(0, 1),
                                       vc.swapaxes(0, 1)))
        out = acc / l.transpose(0, 3, 1, 2)[..., None]
        return out.reshape(B, c, H * Dh).astype(q.dtype)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qg.swapaxes(0, 1)))
    return outs.swapaxes(0, 1).reshape(B, S, H * Dh)


def attention(x, p, cfg: ModelConfig, *, positions, mode="causal",
              cache=None, layer_cache=None, cross_kv=None, window=None):
    """Returns (out, new_layer_cache).

    mode: causal | bidir | cross | decode.  ``layer_cache`` for decode is
    a dict with k, v (B, Smax, Hkv, Dh), pos_slots (Smax,) for ring
    buffers, and length (scalar).
    """
    B, S, _ = x.shape
    win = cfg.sliding_window if window is None else window
    if mode == "cross":
        hd = cfg.hd
        q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k, v = cross_kv
        out = _sdpa(q, k, v, cfg, causal=False)
        return out @ p["wo"], None

    if mode == "decode":
        length = layer_cache["length"]
        positions = jnp.reshape(positions, (1,))
        q, k_new, v_new = _qkv(x, p, cfg, positions)
        Smax = layer_cache["k"].shape[1]
        slot = length % Smax                      # ring for SWA caches
        k = jax.lax.dynamic_update_slice(
            layer_cache["k"], k_new, (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(
            layer_cache["v"], v_new, (0, slot, 0, 0))
        pos_slots = jax.lax.dynamic_update_slice(
            layer_cache["pos_slots"], positions.reshape(1), (slot,))
        kpos = pos_slots[None, :]                # (1, Smax)
        qpos = positions.reshape(1, 1)
        scores_mask = (kpos <= qpos) & (kpos > qpos - (win or 1 << 30))
        valid = jnp.arange(Smax)[None, :] <= length
        mask = scores_mask & valid
        G = cfg.q_per_kv
        qg = q.reshape(B, S, cfg.n_kv_heads, G, cfg.hd)
        scores = (jnp.einsum("bskgd,btkd->bkgst", qg, k)
                  / math.sqrt(cfg.hd)).astype(jnp.float32)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
        out = out.reshape(B, S, cfg.n_heads * cfg.hd) @ p["wo"]
        new_cache = {"k": k, "v": v, "pos_slots": pos_slots,
                     "length": length + 1}
        return out, new_cache

    q, k, v = _qkv(x, p, cfg, positions)
    if mode == "bidir":
        out = _sdpa(q, k, v, cfg, causal=False)
    elif S > 2 * cfg.attn_chunk and S % cfg.attn_chunk == 0:
        out = _sdpa_chunked(q, k, v, cfg, window=win)
    else:
        out = _sdpa(q, k, v, cfg, causal=True, window=win)
    out = out @ p["wo"]
    if mode == "prefill":
        # return the populated cache (pad to S; serving layer resizes)
        pos_slots = positions[0] if positions.ndim > 1 else positions
        new_cache = {"k": k, "v": v, "pos_slots": pos_slots,
                     "length": jnp.asarray(S, jnp.int32)}
        return out, new_cache
    return out, None


def init_attn_cache(cfg: ModelConfig, batch: int, smax: int, dtype):
    return {
        "k": jnp.zeros((batch, smax, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((batch, smax, cfg.n_kv_heads, cfg.hd), dtype),
        "pos_slots": jnp.full((smax,), -1, jnp.int32),
        "length": jnp.asarray(0, jnp.int32),
    }


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             dtype=jnp.float32) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w1": _dense_init(ks[0], (d, f), d, dtype),
         "w2": _dense_init(ks[1], (f, d), f, dtype)}
    if cfg.act in ("silu", "geglu"):
        p["w3"] = _dense_init(ks[2], (d, f), d, dtype)
    return p


def mlp(x, p, cfg: ModelConfig):
    h = x @ p["w1"]
    if cfg.act == "silu":
        h = jax.nn.silu(h) * (x @ p["w3"])
    elif cfg.act == "geglu":
        h = jax.nn.gelu(h) * (x @ p["w3"])
    else:
        h = jax.nn.gelu(h)
    h = dctx.constrain(h, "act_btf")
    return h @ p["w2"]


# ---------------------------------------------------------------------------
# MoE FFN
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, e), d, dtype),
        "w1": _dense_init(ks[1], (e, d, f), d, dtype),
        "w3": _dense_init(ks[2], (e, d, f), d, dtype),
        "w2": _dense_init(ks[3], (e, f, d), f, dtype),
    }
    if cfg.moe_dense_residual:
        sub = dataclasses.replace(cfg, d_ff=cfg.moe_dense_ff or cfg.d_ff)
        p["dense"] = init_mlp(ks[4], sub, dtype=dtype)
    return p


def _expert_ffn(xe, w1, w3, w2):
    """xe: (E, C, D); weights (E, D, F) / (E, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", xe, w1)
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _moe_reference(x2, p, cfg: ModelConfig):
    """Single-device GShard-style dispatch (oracle for the EP path)."""
    T, D = x2.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = x2 @ p["router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    C = max(int(math.ceil(T * k * cfg.capacity_factor / E)), 1)
    # position of each (token, choice) within its expert queue
    onehot_e = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # (T,k,E)
    pos = (jnp.cumsum(onehot_e.reshape(T * k, E), axis=0)
           - onehot_e.reshape(T * k, E)).reshape(T, k, E)
    pos = (pos * onehot_e).sum(-1)                          # (T, k)
    keep = pos < C
    disp = (jax.nn.one_hot(gate_idx, E, dtype=x2.dtype)
            * keep[..., None]
            )[:, :, :, None] * jax.nn.one_hot(pos, C, dtype=x2.dtype
                                              )[:, :, None, :]
    dispatch = disp.sum(1)                                  # (T, E, C)
    combine = dispatch * 0
    combine = (disp * gate_vals[:, :, None, None].astype(x2.dtype)
               ).sum(1)                                     # (T, E, C)
    xe = jnp.einsum("tec,td->ecd", dispatch, x2)
    ye = _expert_ffn(xe, p["w1"], p["w3"], p["w2"])
    return jnp.einsum("tec,ecd->td", combine, ye)


def _moe_ep_shard_map(x2, p, cfg: ModelConfig, ctx: dctx.ShardCtx):
    """Production path: tokens sharded over every mesh axis, experts over
    the model axis with FSDP (F-dim) resharding gathered per use;
    dispatch/return via explicit all-to-all (HitGraph's crossbar analogue
    — DESIGN.md §2)."""
    mesh = ctx.mesh
    tok_axes = tuple(a for a in (*ctx.token_axes, ctx.expert_axis)
                     if a in mesh.axis_names)
    # hierarchical FSDP: expert weights are F-sharded over 'data' only
    # (replicated across pods) so gathers ride intra-pod ICI
    fsdp_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
    n_tok_shards = int(np.prod([mesh.shape[a] for a in tok_axes]))
    n_model = mesh.shape[ctx.expert_axis]
    T, D = x2.shape
    E, k = cfg.n_experts, cfg.top_k
    Tl = T // n_tok_shards
    C = max(int(math.ceil(Tl * k * cfg.capacity_factor / E)), 1)

    def local_moe(x_l, router, w1, w3, w2):
        # x_l: (Tl, D); router (D, E); w* sharded (E_l, D, F/fsdp)
        w1 = jax.lax.all_gather(w1, fsdp_axes, axis=2, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axes, axis=2, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=1, tiled=True)
        logits = x_l @ router
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
        onehot_e = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot_e.reshape(Tl * k, E), 0)
               - onehot_e.reshape(Tl * k, E)).reshape(Tl, k, E)
        pos = (pos * onehot_e).sum(-1)
        keep = pos < C
        oh = (jax.nn.one_hot(gate_idx, E, dtype=x_l.dtype)
              * keep[..., None])
        ohc = jax.nn.one_hot(pos, C, dtype=x_l.dtype)
        disp = (oh[:, :, :, None] * ohc[:, :, None, :])
        dispatch = disp.sum(1)                           # (Tl, E, C)
        combine = (disp * gate_vals[..., None, None].astype(x_l.dtype)
                   ).sum(1)
        send = jnp.einsum("tec,td->ecd", dispatch, x_l)  # (E, C, D)
        recv = jax.lax.all_to_all(send, ctx.expert_axis, split_axis=0,
                                  concat_axis=1, tiled=True)
        ye = _expert_ffn(recv, w1, w3, w2)               # (E_l, C*nm, D)
        back = jax.lax.all_to_all(ye, ctx.expert_axis, split_axis=1,
                                  concat_axis=0, tiled=True)
        return jnp.einsum("tec,ecd->td", combine, back)

    fs = fsdp_axes if fsdp_axes else None
    fx = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(tok_axes, None), P(None, None),
                  P(ctx.expert_axis, None, fs),
                  P(ctx.expert_axis, None, fs),
                  P(ctx.expert_axis, fs, None)),
        out_specs=P(tok_axes, None),
        check_vma=False,
    )
    return fx(x2, p["router"], p["w1"], p["w3"], p["w2"])


def _moe_ep_psum(x2, p, cfg: ModelConfig, ctx: dctx.ShardCtx):
    """Decode-scale EP: tokens sharded over the token axes only and
    replicated over the expert (model) axis; each model shard computes
    its local experts' contributions for all its tokens and the combine
    is a psum over the expert axis.  No all-to-all — the right trade at
    small token counts where per-(shard,expert) capacities round to 0."""
    mesh = ctx.mesh
    tok_axes = tuple(a for a in ctx.token_axes if a in mesh.axis_names)
    fsdp_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
    n_tok_shards = int(np.prod([mesh.shape[a] for a in tok_axes]))
    n_model = mesh.shape[ctx.expert_axis]
    T, D = x2.shape
    E, k = cfg.n_experts, cfg.top_k
    E_l = E // n_model
    Tl = T // n_tok_shards
    C = max(int(math.ceil(Tl * k * cfg.capacity_factor / E)), 1)

    def local_moe(x_l, router, w1, w3, w2):
        w1 = jax.lax.all_gather(w1, fsdp_axes, axis=2, tiled=True)
        w3 = jax.lax.all_gather(w3, fsdp_axes, axis=2, tiled=True)
        w2 = jax.lax.all_gather(w2, fsdp_axes, axis=1, tiled=True)
        e0 = jax.lax.axis_index(ctx.expert_axis) * E_l
        logits = x_l @ router
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)      # global experts
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
        local_idx = gate_idx - e0                          # (Tl, k)
        in_range = (local_idx >= 0) & (local_idx < E_l)
        oh = (jax.nn.one_hot(jnp.where(in_range, local_idx, E_l),
                             E_l + 1, dtype=x_l.dtype)[..., :E_l])
        pos = (jnp.cumsum(oh.reshape(Tl * k, E_l), 0)
               - oh.reshape(Tl * k, E_l)).reshape(Tl, k, E_l)
        pos = (pos * oh).sum(-1).astype(jnp.int32)
        keep = pos < C
        oh = oh * keep[..., None]
        ohc = jax.nn.one_hot(pos, C, dtype=x_l.dtype)
        disp = oh[:, :, :, None] * ohc[:, :, None, :]
        dispatch = disp.sum(1)                             # (Tl, E_l, C)
        combine = (disp * gate_vals[..., None, None].astype(x_l.dtype)
                   ).sum(1)
        xe = jnp.einsum("tec,td->ecd", dispatch, x_l)
        ye = _expert_ffn(xe, w1, w3, w2)
        y_partial = jnp.einsum("tec,ecd->td", combine, ye)
        return jax.lax.psum(y_partial, ctx.expert_axis)

    fs = fsdp_axes if fsdp_axes else None
    fx = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(tok_axes, None), P(None, None),
                  P(ctx.expert_axis, None, fs),
                  P(ctx.expert_axis, None, fs),
                  P(ctx.expert_axis, fs, None)),
        out_specs=P(tok_axes, None),
        check_vma=False,
    )
    return fx(x2, p["router"], p["w1"], p["w3"], p["w2"])


def moe_ffn(x, p, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    ctx = dctx.current()
    mode = "reference"
    if ctx is not None:
        all_axes = tuple(a for a in (*ctx.token_axes, ctx.expert_axis)
                         if a in ctx.mesh.axis_names)
        tok_axes = tuple(a for a in ctx.token_axes
                         if a in ctx.mesh.axis_names)
        n_all = int(np.prod([ctx.mesh.shape[a] for a in all_axes]))
        n_tok = int(np.prod([ctx.mesh.shape[a] for a in tok_axes]))
        n_model = ctx.mesh.shape[ctx.expert_axis]
        if (B * S) % n_all == 0 and (B * S) // n_all >= 1 \
                and cfg.n_experts % n_model == 0:
            mode = "a2a"            # train/prefill: all-to-all dispatch
        elif (B * S) % n_tok == 0 and cfg.n_experts % n_model == 0:
            mode = "psum"           # decode: replicated-dispatch EP
    if mode == "a2a":
        y = _moe_ep_shard_map(x2, p, cfg, ctx)
    elif mode == "psum":
        y = _moe_ep_psum(x2, p, cfg, ctx)
    else:
        y = _moe_reference(x2, p, cfg)
    y = y.reshape(B, S, D)
    if cfg.moe_dense_residual:
        sub = dataclasses.replace(cfg, d_ff=cfg.moe_dense_ff or cfg.d_ff)
        y = y + mlp(x, p["dense"], sub)
    return y
