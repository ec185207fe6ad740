"""The serve path compiles for a TPU v5e chip.

Compiles, for a described (not attached) ``v5e:2x2`` topology, the two
serve implementations at the geometries the memory presets ship and at
both fused-scan chunk sizes: the Pallas serve kernel (not in interpret
mode) and the XLA scans, single and timing-batched.  What Mosaic or XLA
would refuse on the chip — an unaligned block, a bool reshape, more
scoped VMEM than the limit, more HBM than the chip has — fails here.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import vectorized as vec
from repro.kernels.dram_timing import ops
from repro.sim.memory import MemoryConfig

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9

SMALL, LARGE = vec.CHUNK_LADDER

#: (memory kind, K, chunk steps): every preset geometry (C = 1, 4, 8,
#: 16) at both block widths and both chunk-ladder sizes
SHAPES = [(kind, K, S) for kind in ("ddr4", "ddr3", "hbm2", "hbm2e")
          for K in (1, 8) for S in (SMALL, LARGE)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip cannot be read back
    # from the persistent cache without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _geometry(kind):
    cfg = MemoryConfig(kind=kind).resolve()
    return cfg.channels, cfg.banks_per_channel, cfg.org.ranks, cfg.org.banks


def _args(sharding, S, C, K, B, R, M=None):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    lead = () if M is None else (M,)
    carry = tuple(sds(lead + s) for s in
                  [(C, B), (C, B), (C,), (C, R, 4), (C, R), (C,)])
    timing = sds(lead + (len(vec.TIMING_FIELDS),))
    return sds((S, C, K)), sds((S, C, K)), sds, timing, carry


def _total_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


@pytest.mark.parametrize("kind,K,S", SHAPES)
def test_serve_kernel_compiles(one_chip, kind, K, S):
    C, B, R, bpr = _geometry(kind)
    issue, meta, sds, timing, carry = _args(one_chip, S, C, K, B, R)
    compiled = ops._dram_serve.lower(
        issue, meta, sds((S,)), timing, *carry, banks_per_rank=bpr,
        tile=ops.SERVE_TILE, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("kind,K,S", SHAPES)
def test_fused_scan_compiles(one_chip, kind, K, S):
    C, B, R, _ = _geometry(kind)
    issue, meta, sds, timing, carry = _args(one_chip, S, C, K, B, R)
    compiled = vec._fused_scan.lower(
        issue, meta, sds((S,), jnp.bool_), timing, carry).compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES


def test_timing_grid_scan_fits_one_chip(one_chip):
    """Twelve timing vectors against one HBM2E pack at the large chunk
    (``sweep(batch_memories=True)`` over a speed-grade grid): the
    batched scan's temporaries grow with M and C."""
    C, B, R, _ = _geometry("hbm2e")
    issue, meta, sds, timing, carry = _args(one_chip, LARGE, C, 8, B, R,
                                            M=12)
    compiled = vec._fused_scan_batch_shared.lower(
        issue, meta, sds((LARGE,), jnp.bool_), timing, carry).compile()
    assert _total_bytes(compiled) < V5E_HBM_BYTES
