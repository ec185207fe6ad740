"""Pallas kernels vs pure-jnp oracles (interpret mode; shape/dtype sweeps
+ hypothesis properties)."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import vectorized as vec
from repro.core.accel import pack_program
from repro.core.dram import PRESETS, ddr4_2400r
from repro.core.timing import simulate_trace
from repro.core.trace import SegmentedTrace, Trace
from repro.core.vectorized import pack_channels
from repro.kernels.dram_timing.ops import (dram_serve,
                                           simulate_trace_kernel)
from repro.kernels.dram_timing.ref import dram_serve_ref, dram_timing_ref
from repro.kernels.segment_reduce.ops import segment_reduce
from repro.kernels.segment_reduce.ref import segment_reduce_ref
from repro.kernels.edge_scatter.ops import edge_scatter
from repro.kernels.edge_scatter.ref import edge_scatter_ref
from repro.kernels.spmv_ell.ops import csr_to_ell, spmv_ell
from repro.kernels.spmv_ell.ref import spmv_ell_ref
from repro.graphs.formats import CSR
from repro.graphs.generators import rmat


class TestDramTimingKernel:
    @pytest.mark.parametrize("preset", ["hitgraph", "accugraph", "hbm2"])
    @pytest.mark.parametrize("chunk", [128, 512])
    def test_vs_oracle(self, preset, chunk):
        cfg = PRESETS[preset]()
        rng = np.random.default_rng(1)
        n = 2500
        tr = Trace(rng.integers(0, 1 << 20, n), np.zeros(n, bool),
                   np.sort(rng.integers(0, 4 * n, n)))
        oracle = simulate_trace(tr.line_addr, tr.issue, cfg)
        finish, kind, makespan = simulate_trace_kernel(tr, cfg, chunk=chunk)
        assert makespan == oracle.cycles
        assert int((kind == 0).sum()) == oracle.row_hits
        assert int((kind == 2).sum()) == oracle.row_conflicts

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10**6), n=st.integers(1, 600))
    def test_property_vs_ref(self, seed, n):
        cfg = ddr4_2400r()
        rng = np.random.default_rng(seed)
        tr = Trace(rng.integers(0, 1 << 18, n), np.zeros(n, bool),
                   np.sort(rng.integers(0, 8 * n, n)))
        packed = pack_channels(tr, cfg)
        fr, kr = dram_timing_ref(packed.issue, packed.bank, packed.row,
                                 packed.valid,
                                 vec.timing_params(cfg.timing),
                                 n_banks=cfg.banks_per_channel,
                                 banks_per_rank=cfg.org.banks)
        fk, kk, _ = simulate_trace_kernel(tr, cfg, chunk=128)
        v = packed.valid
        np.testing.assert_array_equal(np.asarray(fr)[v], fk[v])
        np.testing.assert_array_equal(np.asarray(kr)[v], kk[v])


def _random_serve_program(rng, n_phases=5, span=1 << 16, max_n=400,
                          hit_heavy=False):
    phases = []
    for p in range(n_phases):
        n = int(rng.integers(1, max_n))
        pool = 64 if hit_heavy else span
        lines = rng.integers(0, pool, n)
        if hit_heavy:
            lines = np.sort(lines)
        issue = np.sort(rng.integers(0, 4 * n, n))
        phases.append((f"p{p}", lines, np.zeros(n, dtype=bool), issue))
    return SegmentedTrace.from_phases(phases)


class TestDramServeKernel:
    """The serve-path tentpole contract: the Pallas blocked-stream
    kernel is bit-identical to the XLA fused scan on the exact carry /
    ``[S, C, K]`` stream format ``run_program`` serves."""

    def _assert_parity(self, cfg, prog, tile=None):
        packed = pack_program(prog, cfg)
        carry = vec.init_lean_carry(cfg.channels, packed.n_banks,
                                    packed.banks_per_rank)
        state = tuple(carry) + (
            jnp.zeros((cfg.channels,), dtype=jnp.int32),)
        t = vec.timing_params(cfg.timing)
        fin_r, st_r = dram_serve_ref(
            packed.issue, packed.meta, packed.boundary, t, *state,
            banks_per_rank=packed.banks_per_rank)
        kw = dict(banks_per_rank=packed.banks_per_rank)
        if tile is not None:
            kw["tile"] = tile
        fin_k, st_k = dram_serve(packed.issue, packed.meta,
                                 packed.boundary, t, state, **kw)
        np.testing.assert_array_equal(np.asarray(fin_r),
                                      np.asarray(fin_k))
        for a, b in zip(st_r, st_k):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("preset", ["hitgraph", "accugraph", "hbm2"])
    @pytest.mark.parametrize("hit_heavy", [False, True])
    def test_vs_ref_all_block_widths(self, preset, hit_heavy):
        """Both packed block widths (K=8 hit chains and K=1 serialized
        misses) across channel counts 1/4/8."""
        cfg = PRESETS[preset]()
        rng = np.random.default_rng(5 + hit_heavy)
        self._assert_parity(cfg, _random_serve_program(
            rng, hit_heavy=hit_heavy))

    @pytest.mark.parametrize("tile", [128, 512])
    def test_tile_sizes_and_padding(self, tile):
        """S that is not a tile multiple must pad with state-no-op
        invalid steps and stay bit-identical."""
        cfg = ddr4_2400r()
        rng = np.random.default_rng(11)
        self._assert_parity(cfg, _random_serve_program(rng, n_phases=3),
                            tile=tile)

    def test_compiled_tile_must_match_boundary_layout(self):
        """A compiled kernel's 1-D boundary block must be a multiple of
        XLA's 1024-element TPU tile; interpret mode takes any tile."""
        from repro.kernels.dram_timing.kernel import dram_serve_kernel
        C, B, R = 1, 16, 1
        state = tuple(vec.init_lean_carry(C, B, B // R)) + (
            jnp.zeros((C,), dtype=jnp.int32),)
        S = 1024
        with pytest.raises(ValueError, match="tile"):
            dram_serve_kernel(
                jnp.zeros((S, C, 1), jnp.int32),
                jnp.zeros((S, C, 1), jnp.int32), jnp.zeros(S, jnp.int32),
                jnp.asarray(vec.timing_params(ddr4_2400r().timing)),
                *state, banks_per_rank=B // R, tile=512, interpret=False)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10**6), tRRD=st.integers(1, 8),
           tFAW=st.integers(4, 40))
    def test_property_traced_timing(self, seed, tRRD, tFAW):
        """Timing is a traced input of the serve kernel: arbitrary
        speed grades hit the same compiled kernel, bit-identical to the
        scan — including carry chaining across chunks (multi-phase
        streams exercise the in-kernel boundary re-base)."""
        import dataclasses
        base = ddr4_2400r()
        cfg = dataclasses.replace(
            base, timing=dataclasses.replace(base.timing, tRRD=tRRD,
                                             tFAW=tFAW))
        rng = np.random.default_rng(seed)
        self._assert_parity(cfg, _random_serve_program(
            rng, n_phases=4, max_n=200,
            hit_heavy=bool(seed % 2)))


class TestSegmentReduce:
    @pytest.mark.parametrize("op", ["sum", "min", "max"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("m,n,d", [(1000, 300, 1), (513, 128, 4),
                                       (128, 700, 2)])
    def test_sweep(self, op, dtype, m, n, d):
        if op != "sum" and dtype == jnp.bfloat16:
            pytest.skip("min/max oracle fill differs in bf16 inf handling")
        rng = np.random.default_rng(0)
        ids = rng.integers(0, n, m)
        vals = rng.normal(size=(m, d)).astype(np.float32)
        out = segment_reduce(ids, jnp.asarray(vals, dtype), n, op=op)
        ref = segment_reduce_ref(ids, jnp.asarray(vals, dtype), n, op=op)
        # per-problem tolerance: bf16 sums of ~m/n values suffer
        # cancellation near zero -> rtol + matching atol (taxonomy Part E)
        rtol, atol = ((1e-5, 1e-4) if dtype == jnp.float32
                      else (5e-2, 5e-2))
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=rtol, atol=atol)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10**6), m=st.integers(1, 400),
           n=st.integers(1, 300))
    def test_property_sum(self, seed, m, n):
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, n, m)
        vals = rng.normal(size=(m,)).astype(np.float32)
        out = segment_reduce(ids, vals, n, op="sum")
        ref = segment_reduce_ref(ids, jnp.asarray(vals), n, op="sum")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_wcc_step_equivalence(self):
        """The kernel implements one synchronous gather step of WCC."""
        g = rmat(8, 4, seed=0)
        vals = np.arange(g.n, dtype=np.float32)
        out = segment_reduce(g.dst, vals[g.src], g.n, op="min")
        ref = segment_reduce_ref(g.dst, jnp.asarray(vals)[g.src], g.n,
                                 op="min")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestEdgeScatter:
    @pytest.mark.parametrize("op", ["copy", "add", "mul"])
    @pytest.mark.parametrize("m,q", [(500, 256), (128, 1000), (77, 33)])
    def test_sweep(self, op, m, q):
        rng = np.random.default_rng(2)
        src = rng.integers(0, q, m)
        w = rng.integers(1, 5, m).astype(np.float32)
        vals = rng.normal(size=q).astype(np.float32)
        act = (rng.random(q) < 0.5).astype(np.float32)
        upd, valid = edge_scatter(src, w, vals, act, op=op)
        upd_r, valid_r = edge_scatter_ref(
            jnp.asarray(src), jnp.asarray(w), jnp.asarray(vals),
            jnp.asarray(act), op=op)
        np.testing.assert_allclose(np.asarray(upd), np.asarray(upd_r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(valid), np.asarray(valid_r),
                                   rtol=1e-5)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10**6))
    def test_property(self, seed):
        rng = np.random.default_rng(seed)
        m, q = int(rng.integers(1, 300)), int(rng.integers(1, 300))
        src = rng.integers(0, q, m)
        w = rng.normal(size=m).astype(np.float32)
        vals = rng.normal(size=q).astype(np.float32)
        act = np.ones(q, np.float32)
        upd, _ = edge_scatter(src, w, vals, act, op="add")
        np.testing.assert_allclose(np.asarray(upd), vals[src] + w,
                                   rtol=1e-5, atol=1e-5)


class TestSpmvEll:
    @pytest.mark.parametrize("n,k,nx", [(256, 4, 256), (100, 7, 333),
                                        (513, 2, 128)])
    def test_sweep(self, n, k, nx):
        rng = np.random.default_rng(3)
        cols = rng.integers(0, nx, (n, k)).astype(np.int32)
        # random padding slots
        pad_mask = rng.random((n, k)) < 0.2
        cols[pad_mask] = nx
        vals = rng.normal(size=(n, k)).astype(np.float32)
        vals[pad_mask] = 0.0
        x = rng.normal(size=nx).astype(np.float32)
        y = spmv_ell(cols, vals, x)
        y_ref = spmv_ell_ref(jnp.asarray(cols), jnp.asarray(vals),
                             jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                    rtol=1e-4, atol=1e-4)

    def test_csr_spmv_end_to_end(self):
        from repro.algorithms import reference as ref
        g = rmat(8, 4, seed=4).with_unit_weights()
        csr = CSR.from_graph(g)
        csr.weights = np.ones(csr.m, np.float32)
        cols, vals = csr_to_ell(csr)
        x = np.arange(g.n, dtype=np.float32)
        # CSR rows are sources; y[i] = sum over out-neighbors x[j]
        y = spmv_ell(cols, vals, x)
        expect = np.zeros(g.n)
        np.add.at(expect, g.src, x[g.dst])
        np.testing.assert_allclose(np.asarray(y), expect, rtol=1e-5)
