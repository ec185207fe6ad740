"""Session facade: the single public entry point for running simulations.

``simulate(graph, problem, accelerator=..., memory=..., backend=...)``
resolves the accelerator spec, the memory device, and the DRAM backend,
and returns the shared :class:`~repro.core.accel.SimReport`.

:class:`SimSession` binds a graph and caches, across repeated calls:

* **algorithm runs** (the expensive JAX part) by ``spec.algorithm_key``;
* **models** (edge sorts, layout, static streams) by config — with the
  DRAM device reduced to its *geometry + clock*, since model state never
  depends on timing parameters;
* **packed programs** by the same geometry key: packing (and the trace
  emission feeding it) depends only on the DRAM geometry and clock,
  never on timing, so a DDR3-vs-DDR4-vs-HBM *timing* comparison packs
  each (graph, accelerator) point once and replays it against every
  traced timing vector (``pack_cache_hits`` / ``pack_cache_misses``
  count reuse).

All three caches are single-flight and thread-safe: the sharded sweep
executor's workers share one session per graph, and concurrent lookups
of the same key block on the first builder instead of duplicating work.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import Future
from typing import Dict, Optional

from repro import obs
from repro.algorithms.common import Problem, RunResult
from repro.analysis import locks
from repro.core import cache as cache_mod
from repro.core.accel import SimReport, pack_program_auto
from repro.graphs.corpus import GraphLike, resolve_graph
from repro.graphs.formats import Graph
from repro.sim.memory import (CacheLike, MemoryLike, resolve_cache,
                              resolve_memory)
from repro.sim.registry import get_accelerator

# built-in specs register on import
from repro.sim import specs as _specs  # noqa: F401


def _coerce_problem(problem) -> Problem:
    return problem if isinstance(problem, Problem) else Problem(problem)


def resolve_run_config(spec, config=None, memory: MemoryLike = None,
                       cache: CacheLike = None,
                       variant: Optional[str] = None,
                       serve_backend: Optional[str] = None, **overrides):
    """Resolve the effective accelerator config from the public axis
    selectors — the single coercion point :meth:`SimSession.run`, the
    sweep engine's case preparation, and the dynamic-update pipeline
    share (defaults <- config <- overrides <- memory <- variant <-
    cache <- serve_backend)."""
    cfg = spec.make_config(config, memory=resolve_memory(memory),
                           **overrides)
    cfg = spec.apply_variant(cfg, variant)
    cache_cfg = resolve_cache(cache, spec)
    if cache_cfg is not None:
        # after variants: a dram-overriding variant (e.g. AccuGraph
        # "hbm") must not discard the requested on-chip cache
        cfg = spec.make_config(cfg, cache=cache_cfg)
    if serve_backend is not None:
        # serve_backend lives on the DRAMConfig and is timing-only
        # (declared in TIMING_ONLY_FIELDS): pinning it never splits
        # the session's geometry-keyed model/pack caches.
        dram = (cfg.dram_config() if hasattr(cfg, "dram_config")
                else cfg.dram)
        cfg = spec.make_config(cfg, memory=dataclasses.replace(
            dram, serve_backend=serve_backend))
    return cfg


def _dram_cfg_key(spec_name: str, config, include_cache: bool):
    """Cache key for state that depends on the config and the DRAM
    *geometry + clock* but not its timing: the config with ``dram``
    nulled, plus the resolved device's geometry/structure key and clock.
    ``include_cache=True`` keys on ``geometry_key`` (what *packing*
    depends on — the on-chip cache filters requests before packing);
    ``False`` keys on ``structure_key`` (what *trace emission* depends
    on — models are shared across every cache variant of a memory
    point).  ``None`` when the config has no pluggable DRAM or is
    unhashable."""
    if not hasattr(config, "dram_config"):
        return None
    try:
        dram = config.dram_config()
        dram_key = (dram.geometry_key if include_cache
                    else dram.structure_key)
        key = (spec_name, dataclasses.replace(config, dram=None),
               dram_key, dram.clock_ghz)
        hash(key)
        return key
    except (TypeError, dataclasses.FrozenInstanceError):
        return None


class SimSession:
    """A graph bound to caches of algorithm runs, models, and packs.

    >>> sess = SimSession(g)
    >>> sess.run(Problem.WCC, accelerator="hitgraph")
    >>> sess.run(Problem.WCC, accelerator="hitgraph", memory="hbm2")
    # second call reuses the edge-centric WCC execution
    """

    #: max packed programs retained per session — packs are the largest
    #: cached artifact ([S, C, K] streams), so the cache is bounded with
    #: insertion-order eviction; in-flight references stay alive through
    #: normal GC, only reuse beyond the window re-packs.
    PACK_CACHE_CAP = 256

    def __init__(self, graph: GraphLike):
        # corpus preset names resolve here, so a session can be opened
        # directly on a scenario: ``SimSession("powerlaw-social")``
        self.graph = resolve_graph(graph)
        # race-instrumented under REPRO_ANALYSIS_LOCKS=1 — every access
        # to the three single-flight caches must hold the session lock
        self._lock = locks.make_lock("session")
        self._runs: Dict[object, Future] = \
            locks.make_dict("SimSession._runs", self._lock)
        self._models: Dict[object, Future] = \
            locks.make_dict("SimSession._models", self._lock)
        self._packs: Dict[object, Future] = \
            locks.make_dict("SimSession._packs", self._lock)
        self.algo_runs = 0
        self.algo_cache_hits = 0
        self.pack_cache_hits = 0
        self.pack_cache_misses = 0
        self.invalidations = 0
        self.invalidation_skips = 0

    def _singleflight(self, cache: Dict[object, Future], key, build,
                      count=None):
        """Get-or-build ``cache[key]`` with single-flight semantics:
        exactly one thread runs ``build()`` per key; concurrent lookups
        wait on its Future.  ``count`` is an optional ``(miss_attr,
        hit_attr)`` counter pair."""
        with self._lock:
            fut = cache.get(key)
            owner = fut is None
            if owner:
                fut = cache[key] = Future()
            if count is not None:
                attr = count[0] if owner else count[1]
                setattr(self, attr, getattr(self, attr) + 1)
        if owner:
            try:
                fut.set_result(build())
            except BaseException as e:
                with self._lock:
                    cache.pop(key, None)
                fut.set_exception(e)
        return fut.result()

    def model_for(self, spec, config):
        """Graph-bound model cache: model construction (edge sorts,
        layout, static streams) is shared across problems/backends — and,
        since model state depends on the DRAM device only through its
        structure and clock, across every timing AND cache variant of
        one memory point (the cache filter runs downstream of trace
        emission)."""
        def _build():
            with obs.span(obs.TRACE_BUILD):
                return spec.build_model(self.graph, config)

        key = _dram_cfg_key(spec.name, config, include_cache=False)
        if key is None:
            try:
                key = (spec.name, config)
                hash(key)
            except TypeError:
                return _build()
        return self._singleflight(self._models, key, _build)

    def algorithm_run(self, spec, problem: Problem, config, root: int,
                      fixed_iters: Optional[int]) -> RunResult:
        def _build():
            with obs.span(obs.ALGORITHM):
                return spec.run_algorithm(self.graph, problem, config,
                                          root=root,
                                          fixed_iters=fixed_iters)

        key = spec.algorithm_key(self.graph, problem, config, root=root,
                                 fixed_iters=fixed_iters)
        return self._singleflight(self._runs, key, _build,
                                  count=("algo_runs", "algo_cache_hits"))

    def packed_program_for(self, spec, problem: Problem, config, model,
                           run: RunResult, dram, root: int = 0,
                           fixed_iters: Optional[int] = None):
        """Geometry-keyed packed-program cache; returns ``(packed,
        cache_stats)`` where ``cache_stats`` describes the on-chip
        hierarchy filtering the program went through before packing
        (``None`` when the device has no cache).

        The cached pack carries whatever timing vector it was first built
        with — callers must serve it with *their* case's traced timing
        (``core.accel.serve_packed(packed, timing=...)``), which is
        exactly what makes the cache sound: nothing in the packed arrays
        (nor the cache filter, which sees only addresses, program order,
        and timing-independent issue bounds) depends on timing."""
        def _build():
            with obs.span(obs.TRACE_BUILD):
                program = model.build_program(problem, run)
            cs = None
            if dram.cache is not None and dram.cache.enabled:
                with obs.span(obs.CACHE_FILTER):
                    program, cs, _ = cache_mod.filter_program(
                        program, dram.cache)
            return pack_program_auto(program, dram), cs

        cfg_key = _dram_cfg_key(spec.name, config, include_cache=True)
        if cfg_key is None:
            with self._lock:
                self.pack_cache_misses += 1
            return _build()
        key = (cfg_key, spec.algorithm_key(
            self.graph, problem, config, root=root,
            fixed_iters=fixed_iters))
        packed = self._singleflight(
            self._packs, key, _build,
            count=("pack_cache_misses", "pack_cache_hits"))
        with self._lock:
            while len(self._packs) > self.PACK_CACHE_CAP:
                oldest = next(iter(self._packs))
                if oldest == key or not self._packs[oldest].done():
                    break
                del self._packs[oldest]
        return packed

    def invalidate(self, touched_partitions) -> int:
        """Invalidate the session's run/model/pack caches after the bound
        graph mutated, keyed by which partitions actually changed: an
        empty ``touched_partitions`` is a guaranteed no-op (every cached
        artifact stays hit — the static prefix of a dynamic run, and any
        zero-impact batch, never repays warm state), a non-empty one
        drops all entries (they are whole-graph artifacts).  The
        per-partition granularity lives one level down, in
        :func:`repro.core.cache.invalidate_lines` over the on-chip
        state.  Returns the number of cache entries dropped."""
        if len(touched_partitions) == 0:
            with self._lock:
                self.invalidation_skips += 1
            return 0
        with self._lock:
            dropped = (len(self._runs) + len(self._models)
                       + len(self._packs))
            self._runs.clear()
            self._models.clear()
            self._packs.clear()
            self.invalidations += 1
        return dropped

    def rebind(self, graph: GraphLike, touched_partitions) -> int:
        """Swap the resident graph (the serve layer's update-batch jobs:
        a long-lived session whose graph evolves in place) and invalidate
        accordingly.  Returns the number of cache entries dropped."""
        dropped = self.invalidate(touched_partitions)
        self.graph = resolve_graph(graph)
        return dropped

    def run(self, problem, accelerator: str = "hitgraph", *,
            config=None, memory: MemoryLike = None,
            cache: CacheLike = None,
            backend: Optional[str] = None, variant: Optional[str] = None,
            serve_backend: Optional[str] = None,
            root: int = 0, fixed_iters: Optional[int] = None,
            **overrides) -> SimReport:
        problem = _coerce_problem(problem)
        spec = get_accelerator(accelerator)
        cfg = resolve_run_config(spec, config, memory=memory, cache=cache,
                                 variant=variant,
                                 serve_backend=serve_backend, **overrides)
        run = self.algorithm_run(spec, problem, cfg, root, fixed_iters)
        return spec.simulate(self.graph, problem, cfg, backend=backend,
                             root=root, fixed_iters=fixed_iters, run=run,
                             model=self.model_for(spec, cfg))


def simulate(graph: GraphLike, problem=None,
             accelerator: str = "hitgraph", *,
             config=None, memory: MemoryLike = None,
             cache: CacheLike = None,
             backend: Optional[str] = None, variant: Optional[str] = None,
             serve_backend: Optional[str] = None,
             root: int = 0, fixed_iters: Optional[int] = None,
             updates=None, **overrides) -> SimReport:
    """Run one simulation through the spec registry.

    Parameters
    ----------
    graph:        a :class:`Graph` instance, a corpus preset name
                  (``"karate"``, ``"powerlaw-social:degree"``, ... —
                  see :data:`repro.graphs.corpus.GRAPH_PRESETS`), or a
                  :class:`~repro.sim.scenario.ScenarioSpec` bundling
                  every scenario axis (the preferred form; the per-axis
                  keywords below stay as a deprecated adapter).
    problem:      a :class:`Problem` or its string value (``"wcc"``...).
    accelerator:  registered name (see :func:`list_accelerators`) or an
                  :class:`AcceleratorSpec` instance.
    config:       accelerator config dataclass (defaults per paper Tab. 4);
                  extra keyword arguments override individual fields, e.g.
                  ``simulate(g, "wcc", partition_elements=2048)``.
    memory:       ``None`` (the accelerator's paper default) or any
                  selector accepted by :func:`resolve_memory` — a preset
                  name (``"ddr3"``, ``"ddr4-8gb"``, ``"hbm2"``...), a
                  :class:`MemoryConfig`, or a raw :class:`DRAMConfig`.
    cache:        on-chip hierarchy level in front of the DRAM device:
                  ``None`` (no cache, unless the memory selector carries
                  one), a :data:`~repro.sim.memory.CACHE_PRESETS` name
                  (``"vertex-1m"``, ``"prefetch-8"``...), ``"default"``
                  (the accelerator's declared paper hierarchy —
                  AccuGraph's vertex BRAM, HitGraph's stream prefetch),
                  or a :class:`~repro.core.cache.CacheConfig`.
    backend:      ``"vectorized"`` (JAX scan fast path), ``"event"``
                  (element-granularity reference; slow), or ``None`` for
                  the accelerator's preferred backend.
    variant:      named optimization variant of the accelerator
                  (``spec.variants()``), e.g. ``"prefetch_skip"``.
    serve_backend: fused-scan serve implementation on the vectorized
                  path: ``"auto"`` (Pallas kernel on TPU/GPU, XLA scan
                  on CPU), ``"scan"``, or ``"pallas"`` — bit-identical
                  results, execution speed only.  ``None`` keeps the
                  memory point's own ``DRAMConfig.serve_backend``
                  (default ``"auto"``).
    updates:      dynamic-graph mutation stream (``None`` = static, or
                  an :data:`~repro.graphs.updates.UPDATE_PRESETS` name /
                  :class:`~repro.graphs.updates.UpdateStream`): the run
                  goes through :func:`repro.sim.dynamic.run_dynamic`
                  and returns its aggregate report over all epochs.

    ``backend`` / ``serve_backend`` are execution knobs, not scenario
    axes — they stay keywords even for the ``ScenarioSpec`` form.
    """
    from repro.sim.policy import resolve_partitioned_config
    from repro.sim.scenario import coerce_scenario
    spec = coerce_scenario(
        "simulate", graph, problem, accelerator=accelerator,
        config=config, memory=memory, cache=cache, variant=variant,
        updates=updates, root=root, fixed_iters=fixed_iters)
    g = resolve_graph(spec.resolved_graph(), scale=spec.graph_scale,
                      seed=spec.graph_seed)
    cfg = resolve_partitioned_config(spec.resolved_config(), g)
    if spec.updates is not None:
        from repro.sim.dynamic import run_dynamic
        return run_dynamic(
            g, spec.problem, updates=spec.updates,
            accelerator=spec.accelerator, config=cfg,
            memory=spec.memory, cache=spec.cache, backend=backend,
            variant=spec.variant, serve_backend=serve_backend,
            root=spec.root, fixed_iters=spec.fixed_iters,
            **overrides).report
    return SimSession(g).run(
        spec.problem, spec.accelerator, config=cfg,
        memory=spec.memory, cache=spec.cache,
        backend=backend, variant=spec.variant,
        serve_backend=serve_backend, root=spec.root,
        fixed_iters=spec.fixed_iters, **overrides)
