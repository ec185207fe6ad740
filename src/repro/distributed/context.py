"""Sharding context: a thin registry the model layers consult.

Layers never import mesh machinery directly; the train/serve step builders
install a :class:`ShardCtx` and layers call :func:`constrain` with logical
names.  Without a context (CPU smoke tests) everything is a no-op, so the
same model code runs single-device and on the 512-chip mesh.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


@dataclasses.dataclass
class ShardCtx:
    mesh: Mesh
    rules: Dict[str, P]
    # axis names used by the manual (shard_map) MoE path
    token_axes: tuple = ("pod", "data")
    expert_axis: str = "model"

    def spec(self, name: str) -> Optional[P]:
        return self.rules.get(name)


def current() -> Optional[ShardCtx]:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def use(ctx: Optional[ShardCtx]):
    prev = current()
    _STATE.ctx = ctx
    try:
        yield
    finally:
        _STATE.ctx = prev


def constrain(x, name: str):
    """Apply a named sharding constraint if a context is installed."""
    ctx = current()
    if ctx is None:
        return x
    spec = ctx.spec(name)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# Default logical-activation rules for the production mesh.  Batch is
# data-parallel over (pod, data); heads / ffn / vocab are tensor-parallel
# over model; decode KV cache is sequence-sharded over model (DESIGN §5).
def default_rules(multi_pod: bool) -> Dict[str, P]:
    batch = ("pod", "data") if multi_pod else ("data",)
    return {
        "tokens": P(batch, None),
        "act_btd": P(batch, None, None),
        "act_btf": P(batch, None, "model"),
        "act_heads": P(batch, None, "model", None),
        "logits": P(batch, None, "model"),
        "kv_cache": P(None, batch, None, "model", None),
        "ssm_state": P(None, batch, "model", None),
    }
