"""Device-resident program packing, the geometry-keyed pack cache, and
the sharded sweep executor.

* field-by-field parity of the jitted device pack against the NumPy
  reference packer (``pack_program`` stays the bit-equivalence oracle);
* SimReport A/B equality of host- vs device-packed serving over a
  36-scenario grid (graphs x problems x accelerators x memories);
* determinism of ``Sweeper(workers=N)`` for N in {1, 2, 4};
* pack-cache reuse: a timing-comparison grid packs each
  (graph, accelerator) point exactly once.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vectorized as vec
from repro.core.accel import (VectorizedDRAM, device_pack_supported,
                              device_row_kinds, finalize_program,
                              finalize_program_device, pack_program,
                              pack_program_device)
from repro.core.dram import PRESETS
from repro.core.trace import SegmentedTrace
from repro.graphs.generators import rmat
from repro.sim import (SimSession, SweepCase, Sweeper, simulate, sweep,
                       timing_variants)


def _random_program(rng, n_phases=5, span=1 << 18, max_n=300,
                    sequential=False):
    phases = []
    base = 0
    for p in range(n_phases):
        n = int(rng.integers(16, max_n))
        if sequential:                    # hit-dominated (wide blocks)
            lines = base + np.arange(n)
            base += n // 2
        else:
            lines = rng.integers(0, span, n)
        phases.append((f"p{p}", lines, np.zeros(n, dtype=bool),
                       np.sort(rng.integers(0, 4 * n, n))))
    return SegmentedTrace.from_phases(phases)


def _phase_tuples(report_or_backend):
    return [(p.name, p.requests, p.start_cycle, p.end_cycle, p.row_hits,
             p.row_conflicts) for p in report_or_backend.phases]


def _segmented(lines_per_phase, rng):
    """A program of the given per-phase line addresses, empty phases
    kept (``from_phases`` drops them), issue cycles sorted at random."""
    lens = [len(x) for x in lines_per_phase]
    offsets = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    issue = np.concatenate([np.sort(rng.integers(0, 4 * n + 1, n))
                            for n in lens])
    line = np.concatenate(lines_per_phase).astype(np.int64)
    return SegmentedTrace(line, np.zeros(len(line), dtype=bool),
                          issue.astype(np.int64), offsets,
                          [f"p{p}" for p in range(len(lens))])


def _parity_programs(case, rng):
    """``(program, earlier program or None)`` of a parity case; the
    earlier program's final row state enters the program."""
    if case in (False, True):
        return _random_program(rng, sequential=case), None
    if case == "miss_heavy":            # a new row almost every request
        return _random_program(rng, span=1 << 26, max_n=600), None
    if case == "long_runs":             # runs of 12-40 hits on one row
        return _segmented(
            [np.repeat(rng.integers(0, 1 << 18, 12),
                       rng.integers(12, 40, 12)) for _ in range(4)],
            rng), None
    if case == "empty_phase":           # first, middle and last phases
        return _segmented(
            [rng.integers(0, 1 << 12, n) for n in (0, 90, 0, 0, 140, 0)],
            rng), None
    if case in ("n_pow2", "n_pow2_plus_1"):
        n = 1024 + (case == "n_pow2_plus_1")
        cut = np.sort(rng.choice(np.arange(1, n), 2, replace=False))
        lines = np.arange(n) // 3 + rng.integers(0, 1 << 14)
        return _segmented(np.split(lines, cut), rng), None
    if case == "padded_phases":         # P = 9 of P_pad = 16
        return _random_program(rng, n_phases=9), None
    if case == "open_row_in":
        return (_random_program(rng, sequential=True, span=1 << 12),
                _random_program(rng, span=1 << 12))
    raise ValueError(case)


#: the parity cases: the random (``False``) and hit-dominated (``True``)
#: programs, then the shapes the device pack's segment arithmetic has to
#: get right
PARITY_CASES = [False, True, "miss_heavy", "long_runs", "empty_phase",
                "n_pow2", "n_pow2_plus_1", "padded_phases", "open_row_in"]


class TestDevicePackParity:
    """The device pack must reproduce every array of the NumPy reference
    bit-for-bit: blocked streams, boundaries, kinds, and the finish
    times / statistics the fused scan derives from them."""

    @pytest.mark.parametrize("preset", list(PRESETS))
    @pytest.mark.parametrize("case", PARITY_CASES)
    def test_packed_arrays_match(self, preset, case):
        cfg = PRESETS[preset]()
        rng = np.random.default_rng(hash((preset, case)) % 2**31)
        prog, earlier = _parity_programs(case, rng)
        open_h = open_d = None
        if earlier is not None:
            open_h = pack_program(earlier, cfg).open_row_final
            open_d = pack_program_device(earlier, cfg).open_row_final
            assert np.array_equal(np.asarray(open_d), open_h)
        assert device_pack_supported(prog, cfg)
        host = pack_program(prog, cfg, open_row=open_h)
        dev = pack_program_device(prog, cfg, open_row=open_d)
        assert np.array_equal(np.asarray(dev.issue), host.issue)
        assert np.array_equal(np.asarray(dev.meta), host.meta)
        assert np.array_equal(np.asarray(dev.boundary), host.boundary)
        assert np.array_equal(
            np.asarray(device_row_kinds(prog, cfg, open_row=open_d)),
            host.kind)
        assert np.array_equal(np.asarray(dev.open_row_final),
                              host.open_row_final)
        assert dev.n_steps == host.n_steps
        assert dev.signature == (tuple(host.issue.shape), host.n_banks,
                                 host.banks_per_rank)
        P = prog.n_phases
        L_p = np.asarray(dev.L_p)
        assert np.array_equal((np.cumsum(L_p) - L_p)[:P],
                              host.step_starts)
        assert not L_p[P:].any()
        phase = np.repeat(np.arange(P), host.requests)
        for got, want in ((dev.hits_p, host.kind == 0),
                          (dev.confl_p, host.kind == 2)):
            got = np.asarray(got)
            assert np.array_equal(got[:P],
                                  np.bincount(phase, want, minlength=P))
            assert not got[P:].any()
        if case == "miss_heavy":
            assert host.issue.shape[2] == 1
        if case == "long_runs":
            assert host.issue.shape[2] == vec.BLOCK_LANES

    def test_finish_times_and_stats_match(self):
        cfg = PRESETS["comparability"]()
        rng = np.random.default_rng(7)
        prog = _random_program(rng, sequential=True)
        host = pack_program(prog, cfg)
        dev = pack_program_device(prog, cfg)
        carry = vec.init_lean_carry(cfg.channels, host.n_banks,
                                    host.banks_per_rank)
        fin_h, _ = vec.fused_scan(host.issue, host.meta, host.boundary,
                                  host.timing, carry)
        carry = vec.init_lean_carry(cfg.channels, dev.n_banks,
                                    dev.banks_per_rank)
        fin_d, _ = vec.fused_scan(dev.issue, dev.meta, dev.boundary,
                                  dev.timing, carry, as_numpy=False)
        assert finalize_program(host, fin_h) == \
            finalize_program_device(dev, fin_d)

    def test_open_row_chaining_across_programs(self):
        """Carry (open rows + timing state) flows identically whether
        programs are packed on host or device."""
        cfg = PRESETS["hitgraph"]()
        rng = np.random.default_rng(3)
        progs = [_random_program(rng, sequential=bool(i % 2))
                 for i in range(3)]
        a = VectorizedDRAM(cfg, pack_backend="host")
        b = VectorizedDRAM(cfg, pack_backend="device")
        for prog in progs:
            a.run_program(prog)
            b.run_program(prog)
        assert a.now == b.now
        assert _phase_tuples(a) == _phase_tuples(b)
        assert (a.total_requests, a.total_row_hits,
                a.total_row_conflicts) == \
            (b.total_requests, b.total_row_hits, b.total_row_conflicts)

    def test_device_pack_counts_dispatches(self):
        cfg = PRESETS["accugraph"]()
        prog = _random_program(np.random.default_rng(5))
        vec.reset_dispatch_counts()
        pack_program_device(prog, cfg)
        assert vec.dispatch_counts()["device_pack"] == 1


class TestHostDeviceABReports:
    """The 36-scenario A/B set: SimReports must be bit-identical between
    host-packed and device-packed serving."""

    def test_ab_grid(self, monkeypatch):
        graphs = [rmat(8, 5, seed=1).undirected_view(),
                  rmat(9, 4, seed=2).undirected_view(),
                  rmat(7, 7, seed=3).undirected_view()]
        # memory axes fitting each accelerator's channel assignment
        # (HitGraph's 4 PEs need >= 4 channels)
        memories = {"hitgraph": [None, "ddr3", "hbm2"],
                    "accugraph": [None, "ddr4-8gb", "hbm2"]}
        accels = ("hitgraph", "accugraph")
        # wcc across the full memory axis; bfs/sssp on the defaults
        scenarios = (
            [(g, "wcc", a, m)
             for g in graphs for a in accels for m in memories[a]]
            + [(g, p, a, None)
               for g in graphs for p in ("bfs", "sssp") for a in accels]
            + [(graphs[0], "pr", a, m) for a in accels for m in memories[a]]
        )
        assert len(scenarios) == 36
        reports = {}
        for backend in ("host", "device"):
            monkeypatch.setenv("REPRO_PACK_BACKEND", backend)
            for idx, (g, p, a, m) in enumerate(scenarios):
                r = simulate(g, p, accelerator=a, memory=m,
                             partition_elements=128)
                reports.setdefault((idx, p, a, m), []).append(r)
        for s, (rh, rd) in reports.items():
            assert rh.runtime_ns == rd.runtime_ns, s
            assert rh.total_requests == rd.total_requests, s
            assert rh.row_hit_rate == rd.row_hit_rate, s
            assert [dataclasses.astuple(p) for p in rh.phases] == \
                [dataclasses.astuple(p) for p in rd.phases], s


class TestShardedDeterminism:
    def _cases(self):
        g1 = rmat(8, 5, seed=11).undirected_view()
        g2 = rmat(7, 6, seed=12).undirected_view()
        return [SweepCase(graph=g, problem="wcc", accelerator=a,
                          memory=m)
                for g in (g1, g2) for a in ("hitgraph", "accugraph")
                for m in (None, "hbm2")]

    def test_identical_rows_any_worker_count(self):
        cases = self._cases()
        def key(rows):
            return [(r.report.system, r.report.runtime_ns,
                     r.report.total_requests, r.report.row_hit_rate,
                     tuple(dataclasses.astuple(p)
                           for p in r.report.phases))
                    for r in rows]
        results = {}
        for w in (1, 2, 4):
            sw = Sweeper(workers=w)
            results[w] = key(sw.run(cases))
            assert sw.stats.workers == w
            assert sw.stats.cases == len(cases)
        assert results[1] == results[2] == results[4]

    def test_workers_validation(self):
        with pytest.raises(ValueError):
            Sweeper(workers=0)
        sw = Sweeper(workers=2)
        with pytest.raises(ValueError):
            sweep(cases=[], workers=4, sweeper=sw)


class TestPackCacheReuse:
    def test_timing_grid_packs_once_per_point(self):
        """A DDR3/DDR4/HBM2-timing comparison grid packs each
        (graph, accelerator) point exactly once and replays the cached
        pack against every timing vector."""
        g = rmat(8, 5, seed=21).undirected_view()
        mems = timing_variants("ddr4-8gb", kinds=("ddr3", "ddr4", "hbm2"))
        sw = Sweeper(batch_memories=True, workers=2)
        rows = sweep(graphs=[g], problems=["wcc"],
                     accelerators=["hitgraph", "accugraph"],
                     memories=mems, sweeper=sw)
        assert sw.stats.pack_cache_misses == 2        # one per accelerator
        assert sw.stats.pack_cache_hits == 4          # the other 4 cases
        assert sw.stats.batched_cases == 6
        # the timing axis actually changes results
        runtimes = {r.memory: r.report.runtime_ns for r in rows
                    if r.report.system == "accugraph"}
        assert len(set(runtimes.values())) > 1
        # a second pass over the same grid is all hits
        sweep(cases=[SweepCase(graph=g, problem="wcc",
                               accelerator="hitgraph", memory=mems[0])],
              sweeper=None)
        before = sw.stats.pack_cache_misses
        sw.run([SweepCase(graph=g, problem="wcc", accelerator=a,
                          memory=m)
                for a in ("hitgraph", "accugraph") for m in mems])
        assert sw.stats.pack_cache_misses == before

    def test_timing_variants_share_geometry(self):
        mems = timing_variants("ddr4-8gb",
                               kinds=("ddr3", "ddr4-3200", "hbm2e"))
        keys = {m.geometry_key for m in mems}
        assert len(keys) == 1
        assert len({m.timing for m in mems}) == 3
        assert all("-timing" in m.name for m in mems)

    def test_batched_matches_sequential_on_timing_grid(self):
        g = rmat(8, 5, seed=31).undirected_view()
        mems = timing_variants("ddr4", kinds=("ddr3", "ddr4", "hbm2e"))
        kw = dict(graphs=[g], problems=["wcc"],
                  accelerators=["accugraph"], memories=mems)
        batched = sweep(batch_memories=True, workers=2, **kw)
        seq = sweep(**kw)
        for b, s in zip(batched, seq):
            assert b.report.runtime_ns == s.report.runtime_ns
            assert _phase_tuples(b.report) == _phase_tuples(s.report)

    def test_session_cache_counters(self):
        g = rmat(7, 5, seed=41).undirected_view()
        sess = SimSession(g)
        sess.run("wcc", accelerator="accugraph")
        sess.run("wcc", accelerator="accugraph", memory="ddr4")
        # same geometry + clock as the accugraph default -> shared model
        key_count = len(sess._models)
        assert key_count == 1


class TestDevicePackStructure:
    """The classify/block program moves request-length arrays only
    through its two sorts: every scatter, gather and binary search in it
    runs over a few segment keys, not over the requests."""

    N_PAD, P_PAD = 1 << 12, 8

    def _primitives(self, jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from self._primitives(sub)

    @pytest.mark.parametrize("preset", ["hitgraph", "hbm2e"])
    def test_core_has_no_request_length_scatter_or_gather(self, preset):
        cfg = PRESETS[preset]()
        C, B = cfg.channels, cfg.banks_per_channel
        assert max(C * B, self.P_PAD * C) < self.N_PAD
        req = jax.ShapeDtypeStruct((self.N_PAD,), jnp.int32)
        jaxpr = jax.make_jaxpr(functools.partial(
            vec._device_pack_core, spec=cfg.decode_spec(), C=C, B=B,
            banks=cfg.org.banks))(
            req, req, jax.ShapeDtypeStruct((self.P_PAD + 1,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((C, B), jnp.int32))
        eqns = list(self._primitives(jaxpr.jaxpr))
        sorts = [e for e in eqns if e.primitive.name == "sort"]
        assert len(sorts) == 2
        assert all(v.aval.shape == (self.N_PAD,)
                   for e in sorts for v in e.invars)
        scatters = [e for e in eqns
                    if e.primitive.name.startswith("scatter")]
        gathers = [e for e in eqns if e.primitive.name == "gather"]
        assert scatters and gathers
        for e in scatters:                 # (operand, indices, updates)
            assert e.invars[2].aval.size < self.N_PAD, e
        for e in gathers:                  # (operand, indices)
            assert e.invars[1].aval.shape[0] < self.N_PAD, e
