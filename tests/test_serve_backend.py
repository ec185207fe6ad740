"""serve_backend acceptance: ``run_program`` results are bit-identical
between ``serve_backend="scan"`` and ``serve_backend="pallas"``
(interpret mode on CPU) across the full TIMING_PRESETS x CACHE_PRESETS
grid on both accelerators, plus knob plumbing/validation."""

import dataclasses

import pytest

from repro.core import vectorized as vec
from repro.core.dram import DRAMConfig, ddr4_2400r
from repro.sim.memory import (CACHE_PRESETS, TIMING_PRESETS,
                              timing_variants)
from repro.sim.session import SimSession, simulate


class TestBackendParity:
    """The tentpole contract, end to end through ``simulate``."""

    @pytest.mark.parametrize("accel", ["hitgraph", "accugraph"])
    def test_full_timing_cache_grid(self, accel):
        """All TIMING_PRESETS x all CACHE_PRESETS, one accelerator:
        every SimReport field equal between backends.  One session per
        accelerator — packing is geometry-keyed, so the grid reuses
        models/packs and the whole cross costs a few seconds."""
        base = "ddr3" if accel == "hitgraph" else "ddr4"
        sess = SimSession("karate")
        for tname in TIMING_PRESETS:
            mem, = timing_variants(base, kinds=(tname,))
            for cname in CACHE_PRESETS:
                scan = sess.run("wcc", accel, memory=mem, cache=cname,
                                serve_backend="scan")
                pallas = sess.run("wcc", accel, memory=mem, cache=cname,
                                  serve_backend="pallas")
                assert scan == pallas, (accel, tname, cname)

    def test_backend_dispatch_routing(self):
        """The knob actually routes: pallas serves count on the pallas
        dispatch counter, scan serves on the fused counter."""
        vec.reset_dispatch_counts()
        simulate("karate", "wcc", "hitgraph", serve_backend="pallas")
        assert vec.dispatch_counts()["pallas"] > 0
        pallas_only = vec.dispatch_counts()["fused"]
        simulate("karate", "wcc", "hitgraph", serve_backend="scan")
        assert vec.dispatch_counts()["fused"] > pallas_only

    def test_default_matches_explicit_auto(self):
        a = simulate("karate", "pr", "accugraph")
        b = simulate("karate", "pr", "accugraph", serve_backend="auto")
        assert a == b


class TestServeBackendKnob:
    def test_dramconfig_validates(self):
        with pytest.raises(ValueError, match="serve_backend"):
            dataclasses.replace(ddr4_2400r(), serve_backend="nope")

    def test_dramconfig_default_auto(self):
        assert ddr4_2400r().serve_backend == "auto"

    def test_resolve_explicit_wins(self):
        assert vec.resolve_serve_backend("scan") == "scan"
        assert vec.resolve_serve_backend("pallas") == "pallas"
        with pytest.raises(ValueError, match="serve_backend"):
            vec.resolve_serve_backend("interpret")

    def test_resolve_auto_platform(self, monkeypatch):
        monkeypatch.delenv("REPRO_SERVE_BACKEND", raising=False)
        for platform, expect in (("cpu", "scan"), ("tpu", "pallas"),
                                 ("gpu", "scan")):
            monkeypatch.setattr(vec.jax, "default_backend",
                                lambda p=platform: p)
            assert vec.resolve_serve_backend("auto") == expect, platform

    def test_resolve_explicit_pallas_off_tpu_raises(self, monkeypatch):
        monkeypatch.setattr(vec.jax, "default_backend", lambda: "gpu")
        assert vec.resolve_serve_backend("scan") == "scan"
        with pytest.raises(ValueError, match="TPU kernel"):
            vec.resolve_serve_backend("pallas")

    def test_resolve_auto_env_override(self, monkeypatch):
        monkeypatch.setenv("REPRO_SERVE_BACKEND", "pallas")
        assert vec.resolve_serve_backend("auto") == "pallas"
        monkeypatch.setenv("REPRO_SERVE_BACKEND", "scan")
        assert vec.resolve_serve_backend("auto") == "scan"
        # unknown env values are ignored, not raised: the env hook is a
        # soft preference, the explicit arg is the validated surface
        monkeypatch.setenv("REPRO_SERVE_BACKEND", "bogus")
        assert vec.resolve_serve_backend("auto") in ("scan", "pallas")

    def test_timing_only_cache_sharing(self):
        """serve_backend is declared timing-only: flipping it must not
        split the session's structure-keyed model cache (nor re-run the
        algorithm) — both backends replay the same cached artifacts."""
        sess = SimSession("karate")
        sess.run("wcc", "hitgraph", serve_backend="scan")
        assert len(sess._models) == 1
        assert sess.algo_runs == 1
        sess.run("wcc", "hitgraph", serve_backend="pallas")
        assert len(sess._models) == 1
        assert sess.algo_runs == 1
        assert sess.algo_cache_hits == 1

    def test_serve_backend_structure_key_invariant(self):
        """The DRAM structure/geometry keys — what the model and pack
        caches key on — are serve_backend-invariant."""
        import dataclasses as dc
        cfg = ddr4_2400r()
        alt = dc.replace(cfg, serve_backend="pallas")
        assert cfg.structure_key == alt.structure_key
        assert cfg.geometry_key == alt.geometry_key
