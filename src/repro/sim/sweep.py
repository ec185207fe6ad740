"""Batched sweep engine: one call, a grid of simulations, shared work
deduplicated.

``sweep()`` expands a (graph x problem x accelerator x memory x cache x
variant) grid — or takes an explicit case list — and returns one
:class:`SweepRow` per grid point, in grid order.

What is shared and what is not:

* **Algorithm runs** (the JAX engine executions that produce per-iteration
  statistics) are deduplicated across all grid points whose
  ``algorithm_key`` matches — every memory type and every variant that
  does not change the execution (e.g. ``prefetch_skip``, ``hbm``) reuses
  one run per (graph, problem) instead of recomputing it.
* **Models and packed programs** are cached by DRAM *geometry + clock*
  (``DRAMConfig.geometry_key``): neither the trace a model emits nor the
  packed lockstep streams depend on timing parameters, so a timing
  comparison grid (e.g. ``memory.timing_variants``) packs each
  (graph, accelerator) point once and replays it against every traced
  timing vector.  ``SweepStats.pack_cache_hits`` / ``pack_cache_misses``
  count the reuse.
* **Execution is sharded**: ``workers=N`` prepare cases concurrently
  (algorithm run + trace build + device pack) while the serving loop
  drains them onto the device in deterministic case order — rows are
  bit-identical for any worker count.  With ``batch_memories=True``,
  cases whose packed programs share a compiled shape are additionally
  stacked into single ``vmap``-ed fused-scan dispatches.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import defaultdict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterable, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.algorithms.common import Problem
from repro.analysis import locks
from repro.core import vectorized as vec
from repro.core.accel import (DevicePackedProgram, ProgramStats, SimReport,
                              finalize_program, finalize_program_device,
                              serve_packed)
from repro.graphs.corpus import GraphLike, resolve_graph
from repro.graphs.formats import Graph
from repro.graphs.updates import (UpdatesLike, resolve_updates,
                                  updates_name)
from repro.sim.memory import (CacheLike, MemoryLike, cache_name,
                              memory_name, resolve_cache, resolve_memory)
from repro.sim.policy import resolve_partitioned_config
from repro.sim.registry import get_accelerator
from repro.sim.scenario import ScenarioSpec
from repro.sim.session import SimSession, _coerce_problem
from repro.serve import chaos


@dataclasses.dataclass(frozen=True)
class SweepCase:
    """One grid point of a sweep.

    ``graph`` accepts a :class:`Graph` or a corpus preset name
    (``"karate"``, ``"powerlaw-social:degree"``, ... — see
    :data:`repro.graphs.corpus.GRAPH_PRESETS`); names resolve at
    construction through the memoized corpus resolver, so every case
    naming one scenario shares a single graph object (and therefore one
    per-graph session in the sweep engine).

    ``config`` may carry a :class:`~repro.sim.policy.PartitionPolicy` in
    its ``partition_elements`` field (a graph-relative partition count);
    it resolves against the resolved graph here, so every downstream
    consumer (sessions, the service, design-space search) only ever
    sees concrete integer configs.

    Every string axis validates at construction: an unknown accelerator,
    memory, cache, variant, or updates preset raises
    :class:`~repro.errors.UnknownPresetError` naming the axis and the
    nearest valid name here, instead of surfacing later from deep inside
    a worker thread.  ``updates`` names a mutation stream
    (:data:`~repro.graphs.updates.UPDATE_PRESETS` or an
    :class:`~repro.graphs.updates.UpdateStream`); a non-``None`` value
    makes the case dynamic — it runs the epoch pipeline of
    :func:`repro.sim.dynamic.run_dynamic` and yields one aggregate row
    with per-epoch reports attached (:attr:`SweepRow.epochs`).
    """

    graph: GraphLike
    problem: Problem
    accelerator: str = "hitgraph"
    memory: MemoryLike = None
    cache: CacheLike = None
    variant: Optional[str] = None
    config: Any = None
    root: int = 0
    fixed_iters: Optional[int] = None
    graph_scale: float = 1.0
    graph_seed: int = 0
    updates: UpdatesLike = None

    def __post_init__(self):
        object.__setattr__(self, "problem",
                           _coerce_problem(self.problem))
        object.__setattr__(
            self, "graph",
            resolve_graph(self.graph, scale=self.graph_scale,
                          seed=self.graph_seed))
        object.__setattr__(
            self, "config",
            resolve_partitioned_config(self.config, self.graph))
        # fail-fast axis validation (each resolver raises a typed
        # UnknownPresetError naming the axis + nearest preset); the
        # resolved products are rebuilt later where needed — only the
        # updates stream is kept, so one case carries one stream object
        spec = get_accelerator(self.accelerator)
        if self.variant is not None and self.variant not in \
                spec.variants():
            spec.apply_variant(spec.make_config(None), self.variant)
        resolve_memory(self.memory)
        resolve_cache(self.cache, spec)
        object.__setattr__(self, "updates",
                           resolve_updates(self.updates))


def case_chaos_key(case: "SweepCase") -> str:
    """Stable identity of one grid point, used for deterministic fault
    injection and supervisor crash attribution: everything that *names*
    the case, nothing that depends on object identity or scheduling."""
    return "|".join((case.graph.fingerprint, case.problem.value,
                     case.accelerator, memory_name(case.memory),
                     cache_name(case.cache), case.variant or "baseline",
                     str(case.root), str(case.fixed_iters),
                     updates_name(case.updates)))


class SweepInterrupted(RuntimeError):
    """A sweep stopped cooperatively at a case boundary (client cancel,
    deadline expiry, service shutdown).  ``rows`` is the input-aligned
    row list at the moment of interruption — completed cases carry their
    :class:`SweepRow`, unserved ones ``None`` — so callers keep the
    partial results."""

    def __init__(self, reason: str, rows: Sequence[Optional["SweepRow"]]):
        self.reason = reason
        self.rows = list(rows)
        done = sum(r is not None for r in self.rows)
        super().__init__(f"sweep interrupted ({reason}) after "
                         f"{done}/{len(self.rows)} cases")


class SweepError(RuntimeError):
    """A sweep case failed; carries *which* case so grid failures are
    attributable without replaying the sweep (worker errors used to
    surface only at drain time as the bare underlying exception)."""

    def __init__(self, index: int, case: SweepCase, cause: BaseException):
        self.index = index
        self.case = case
        super().__init__(
            f"sweep case #{index} (graph={case.graph.name!r}, "
            f"problem={case.problem.value}, "
            f"accelerator={case.accelerator!r}, "
            f"memory={memory_name(case.memory)}, "
            f"cache={cache_name(case.cache)}, "
            f"variant={case.variant or 'baseline'}) failed: {cause!r}")


@dataclasses.dataclass
class SweepRow:
    """One simulated grid point.  A dynamic case (``case.updates``)
    stays 1:1 with its grid point: ``report`` aggregates the whole
    update timeline and ``epochs`` carries the per-epoch
    :class:`~repro.sim.dynamic.EpochReport` rows (``None`` for static
    cases)."""

    case: SweepCase
    report: SimReport
    wall_s: float
    epochs: Optional[List] = None

    @property
    def graph_name(self) -> str:
        return self.case.graph.name

    @property
    def memory(self) -> str:
        return memory_name(self.case.memory)

    @property
    def cache(self) -> str:
        return cache_name(self.case.cache)

    @property
    def variant(self) -> str:
        return self.case.variant or "baseline"

    @property
    def updates(self) -> str:
        return updates_name(self.case.updates)

    def as_dict(self) -> Dict[str, Any]:
        r = self.report
        out = {
            "graph": self.graph_name, "problem": self.case.problem.value,
            "accelerator": r.system, "memory": self.memory,
            "cache": self.cache, "variant": self.variant,
            "updates": self.updates,
            "runtime_ms": r.runtime_ms,
            "iterations": r.iterations, "reps": r.reps,
            "row_hit_rate": r.row_hit_rate,
            "cache_hit_rate": r.cache_hit_rate,
            "total_requests": r.total_requests, "wall_s": self.wall_s,
        }
        if self.epochs is not None:
            out["epochs"] = len(self.epochs)
            out["edges_inserted"] = sum(e.inserted for e in self.epochs)
            out["edges_deleted"] = sum(e.deleted for e in self.epochs)
            out["cache_lines_invalidated"] = sum(
                e.cache_lines_invalidated for e in self.epochs)
            out["reset_vertices"] = sum(e.reset_vertices
                                        for e in self.epochs)
        return out


@dataclasses.dataclass
class SweepStats:
    cases: int = 0
    algo_runs: int = 0
    algo_cache_hits: int = 0
    pack_cache_hits: int = 0
    pack_cache_misses: int = 0
    batched_cases: int = 0
    batch_dispatches: int = 0
    sharded_dispatches: int = 0
    workers: int = 1
    devices: int = 1


class Sweeper:
    """Executes sweep cases with per-graph algorithm/model/pack caching.

    ``workers=N`` shards case *preparation* (algorithm run, trace build,
    device pack) over N threads; the serving loop drains the prepared
    cases onto the device in deterministic case order, so results are
    identical for any worker count.  With ``batch_memories=True``, cases
    whose packed programs share a compiled shape (same steps x channels x
    banks x ranks — e.g. one accelerator/graph across timing variants)
    are stacked and served by ONE ``vmap``-ed fused-scan dispatch;
    remaining cases fall back to the per-case path.  ``devices=N``
    additionally shards those stacked dispatches over a 1-D case mesh
    (:func:`repro.launch.mesh.make_sweep_mesh`): each device serves its
    slice of the batch with identical per-case math, so rows stay
    bit-identical for ANY (workers, devices) combination.
    """

    def __init__(self, backend: Optional[str] = None,
                 batch_memories: bool = False, workers: int = 1,
                 devices: int = 1):
        if workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        if devices < 1:
            raise ValueError(f"devices must be >= 1, got {devices}")
        self.backend = backend
        self.batch_memories = batch_memories
        self.workers = workers
        self.devices = devices
        self._mesh = None  # built lazily on first sharded dispatch
        # race-instrumented under REPRO_ANALYSIS_LOCKS=1
        self._sessions_lock = locks.make_lock("sweeper-sessions")
        self._sessions: Dict[int, SimSession] = \
            locks.make_dict("Sweeper._sessions", self._sessions_lock)
        self.stats = SweepStats(workers=workers, devices=devices)

    def _sweep_mesh(self):
        """Build (once) the 1-D case mesh for ``devices > 1``.  Lazy so
        a single-device sweeper never imports the distributed stack nor
        touches jax device state."""
        if self._mesh is None:
            from repro.launch.mesh import make_sweep_mesh
            self._mesh = make_sweep_mesh(self.devices)
        return self._mesh

    def _session(self, g: Graph) -> SimSession:
        # worker threads race here via _prepare_case; two sessions for
        # one graph would silently fork the single-flight caches.
        # Keyed by content fingerprint (not id()) so independently
        # resolved copies of one corpus scenario still share algorithm
        # runs, models, and packed programs.
        key = g.fingerprint
        with self._sessions_lock:
            sess = self._sessions.get(key)
            if sess is None:
                sess = self._sessions[key] = SimSession(g)
            return sess

    def _sync_stats(self) -> None:
        """Cache counters live on the (thread-safe) sessions; mirror
        their totals onto the stats surface.

        Called once per :meth:`run` at the drain/return boundary (in a
        ``finally``, so interrupted sweeps surface their partial
        counters too) — NOT per case: re-summing every session's
        counters under the sessions lock after each of N cases is
        O(N x sessions) lock traffic, which the autotuner's large
        generated grids turned into a measurable serialization point.
        """
        with self._sessions_lock:
            sessions = list(self._sessions.values())
        s = self.stats
        s.workers = self.workers
        s.devices = self.devices
        s.algo_runs = sum(x.algo_runs for x in sessions)
        s.algo_cache_hits = sum(x.algo_cache_hits for x in sessions)
        s.pack_cache_hits = sum(x.pack_cache_hits for x in sessions)
        s.pack_cache_misses = sum(
            x.pack_cache_misses for x in sessions)

    def run_case(self, case: SweepCase,
                 backend: Optional[str] = None) -> SweepRow:
        chaos.maybe_inject("dram.serve", case_chaos_key(case))
        sess = self._session(case.graph)
        t0 = time.perf_counter()
        if case.updates is not None:
            # dynamic case: one long-lived memory timeline over the
            # update epochs.  A pure function of the case (the stream is
            # seeded, the session only accelerates the static prefix),
            # so rows stay bit-identical for any (workers, devices).
            from repro.sim.dynamic import run_dynamic
            result = run_dynamic(
                case.graph, case.problem, updates=case.updates,
                accelerator=case.accelerator, config=case.config,
                memory=case.memory, cache=case.cache,
                backend=self.backend if backend is None else backend,
                variant=case.variant, root=case.root,
                fixed_iters=case.fixed_iters, session=sess)
            self.stats.cases += 1
            return SweepRow(case=case, report=result.report,
                            wall_s=time.perf_counter() - t0,
                            epochs=result.epochs)
        report = sess.run(
            case.problem, case.accelerator, config=case.config,
            memory=case.memory, cache=case.cache,
            backend=self.backend if backend is None else backend,
            variant=case.variant, root=case.root,
            fixed_iters=case.fixed_iters)
        wall = time.perf_counter() - t0
        self.stats.cases += 1
        return SweepRow(case=case, report=report, wall_s=wall)

    @staticmethod
    def _guard(index: int, case: SweepCase, fn):
        """Run one case-scoped step; failures re-raise as
        :class:`SweepError` naming the case, so errors raised from
        worker threads stay attributable when they surface at drain
        time (and a poisoned case cannot wedge the executor — the
        exception still propagates through the drained future)."""
        try:
            return fn()
        except SweepError:
            raise
        except Exception as e:
            raise SweepError(index, case, e) from e

    @staticmethod
    def _check_control(control, rows) -> None:
        """Cooperative cancellation checkpoint: ``control`` (a callable
        returning ``None`` to continue or a reason string to stop) is
        polled at every case boundary; tripping raises
        :class:`SweepInterrupted` carrying the rows completed so far."""
        if control is None:
            return
        reason = control()
        if reason:
            raise SweepInterrupted(reason, rows)

    def run(self, cases: Sequence[SweepCase], *, control=None,
            backend: Optional[str] = None) -> List[SweepRow]:
        """Run all cases; rows come back in input order, but execution is
        grouped by (accelerator, graph) for scan/model reuse.

        ``control`` is an optional cancellation probe checked between
        cases (see :meth:`_check_control`); ``backend`` overrides the
        sweeper's backend for this run only (the service's degraded-
        fidelity arm forces ``"vectorized"`` without rebuilding the
        resident sweeper)."""
        cases = list(cases)
        backend = self.backend if backend is None else backend
        # one stats sync per run, at the drain boundary — the finally
        # keeps interrupted/failed sweeps' partial counters visible
        # without paying a per-case re-sum (see _sync_stats)
        with obs.span(obs.SWEEP_RUN):
            try:
                if backend in (None, "vectorized"):
                    if self.batch_memories:
                        rows = self._run_batched(cases, control)
                    else:
                        rows = self._run_pipelined(cases, control)
                else:
                    order = sorted(
                        range(len(cases)),
                        key=lambda i: (cases[i].accelerator,
                                       cases[i].graph.fingerprint))
                    rows = [None] * len(cases)
                    for i in order:
                        self._check_control(control, rows)
                        rows[i] = self._guard(
                            i, cases[i],
                            lambda: self.run_case(cases[i],
                                                  backend=backend))
            finally:
                self._sync_stats()
            return rows

    def _prepare_case(self, case: SweepCase):
        """Build ``(model, run, packed, cache_stats, dram)`` for a
        batchable case, or ``None`` if the accelerator has no program
        form (e.g. the event-driven reference machine).  Thread-safe:
        every expensive product goes through the session's single-flight
        caches, and the (cache-filtered) packed program comes from the
        geometry-keyed pack cache."""
        if case.updates is not None:
            # dynamic cases serialize through run_case on the serving
            # thread in every mode: their epochs share one mutating
            # memory timeline, which the stacked vmap dispatch cannot
            # express (and must not reorder)
            return None
        key = case_chaos_key(case)
        chaos.maybe_inject("worker.crash", key)
        chaos.maybe_inject("sweep.prepare", key)
        sess = self._session(case.graph)
        spec = get_accelerator(case.accelerator)
        cfg = spec.make_config(case.config,
                               memory=resolve_memory(case.memory))
        cfg = spec.apply_variant(cfg, case.variant)
        cache_cfg = resolve_cache(case.cache, spec)
        if cache_cfg is not None:
            # after variants, so dram-overriding variants keep the cache
            cfg = spec.make_config(cfg, cache=cache_cfg)
        model = sess.model_for(spec, cfg)
        if not hasattr(model, "build_program"):
            return None
        run = sess.algorithm_run(spec, case.problem, cfg, case.root,
                                 case.fixed_iters)
        dram = (cfg.dram_config() if hasattr(cfg, "dram_config")
                else model.dram)
        packed, cstats = sess.packed_program_for(
            spec, case.problem, cfg, model, run, dram,
            root=case.root, fixed_iters=case.fixed_iters)
        return model, run, packed, cstats, dram

    def _run_pipelined(self, cases: Sequence[SweepCase],
                       control=None) -> List[SweepRow]:
        """Sharded per-case execution: ``workers`` threads prepare cases
        (algorithm run + trace build + pack — XLA and NumPy release the
        GIL for the heavy parts) while this thread serves the fused scans
        in deterministic case order.  Bit-identical to the sequential
        path for any worker count."""
        order = sorted(
            range(len(cases)),
            key=lambda i: (cases[i].accelerator, cases[i].graph.fingerprint))
        rows: List[Optional[SweepRow]] = [None] * len(cases)

        def prep(i):
            t0 = time.perf_counter()
            out = self._guard(i, cases[i],
                              lambda: self._prepare_case(cases[i]))
            return out, time.perf_counter() - t0

        with ThreadPoolExecutor(max_workers=self.workers) as pool:
            pending = deque()
            it = iter(order)

            def submit_next():
                i = next(it, None)
                if i is not None:
                    pending.append((i, pool.submit(prep, i)))

            # bound the in-flight window so prepared programs don't pile
            # up in memory ahead of the serving loop
            for _ in range(self.workers + 2):
                submit_next()
            try:
                while pending:
                    self._check_control(control, rows)
                    i, fut = pending.popleft()
                    with obs.span(obs.WAIT_PREPARE):
                        prepped, prep_s = fut.result()
                    submit_next()
                    case = cases[i]
                    if prepped is None:
                        rows[i] = self._guard(
                            i, case, lambda: self.run_case(case))
                        continue
                    self.stats.cases += 1
                    model, run_, packed, cstats, dram = prepped
                    t0 = time.perf_counter()

                    def _serve():
                        chaos.maybe_inject("dram.serve",
                                           case_chaos_key(case))
                        if packed is None:
                            return ProgramStats([], 0, 0, 0, 0)
                        s, _ = serve_packed(
                            packed,
                            timing=vec.timing_params(dram.timing),
                            serve_backend=getattr(
                                dram, "serve_backend", "auto"))
                        return s
                    stats = self._guard(i, case, _serve)
                    stats.attach_cache(cstats)
                    rows[i] = SweepRow(
                        case,
                        model.make_report(case.problem, run_, stats),
                        prep_s + time.perf_counter() - t0)
            except BaseException:
                # stop at this case boundary: drop queued preps (running
                # ones finish under the executor's exit) and let the
                # interruption/error propagate with the rows so far
                pool.shutdown(wait=False, cancel_futures=True)
                raise
        return rows

    def _run_batched(self, cases: Sequence[SweepCase],
                     control=None) -> List[SweepRow]:
        rows: List[Optional[SweepRow]] = [None] * len(cases)

        def prep(i):
            t0 = time.perf_counter()
            out = self._guard(i, cases[i],
                              lambda: self._prepare_case(cases[i]))
            return out, time.perf_counter() - t0

        self._check_control(control, rows)
        with obs.span(obs.WAIT_PREPARE), \
                ThreadPoolExecutor(max_workers=self.workers) as pool:
            preps = list(pool.map(prep, range(len(cases))))
        groups = defaultdict(list)
        for i, (prepped, prep_s) in enumerate(preps):
            if prepped is None:
                rows[i] = self._guard(i, cases[i],
                                      lambda: self.run_case(cases[i]))
                continue
            self.stats.cases += 1
            model, run_, packed, cstats, dram = prepped
            sig = packed.signature if packed is not None else None
            groups[sig].append((i, cases[i], model, run_, packed, cstats,
                                dram, prep_s))
        def serve_group(items):
            t0 = time.perf_counter()
            packs = [it[4] for it in items]
            timings = np.stack(
                [vec.timing_params(it[6].timing) for it in items])
            device = all(isinstance(p, DevicePackedProgram)
                         for p in packs)
            # devices > 1: shard the case batch over the 1-D case mesh —
            # same vmapped per-case math on each device's slice, so rows
            # are bit-identical to the single-device dispatch
            shard = self.devices > 1 and len(items) > 1
            if shard:
                from repro.distributed.sharding import (
                    sharded_fused_scan_batch,
                    sharded_fused_scan_batch_shared)
                mesh = self._sweep_mesh()
                self.stats.sharded_dispatches += 1
            if len({id(p) for p in packs}) == 1:
                # one cached pack, many timing vectors: serve the
                # resident program against the whole timing batch
                # without replicating its streams
                if shard:
                    fins, _ = sharded_fused_scan_batch_shared(
                        packs[0].issue, packs[0].meta,
                        packs[0].boundary, timings, packs[0].n_banks,
                        packs[0].banks_per_rank, mesh,
                        as_numpy=not device)
                else:
                    fins, _ = vec.fused_scan_batch_shared(
                        packs[0].issue, packs[0].meta,
                        packs[0].boundary, timings, packs[0].n_banks,
                        packs[0].banks_per_rank, as_numpy=not device)
            else:
                stack = jnp.stack if device else np.stack
                streams = (stack([p.issue for p in packs]),
                           stack([p.meta for p in packs]),
                           stack([p.boundary for p in packs]))
                if shard:
                    fins, _ = sharded_fused_scan_batch(
                        *streams, timings, packs[0].n_banks,
                        packs[0].banks_per_rank, mesh,
                        as_numpy=not device)
                else:
                    fins, _ = vec.fused_scan_batch(
                        *streams, timings, packs[0].n_banks,
                        packs[0].banks_per_rank, as_numpy=not device)
            share = (time.perf_counter() - t0) / len(items)
            for (i, case, model, run_, packed, cstats, _dram,
                 wall), m in zip(items, range(len(items))):
                if isinstance(packed, DevicePackedProgram):
                    stats = finalize_program_device(packed, fins[m])
                else:
                    stats = finalize_program(packed, np.asarray(fins[m]))
                stats.attach_cache(cstats)
                rows[i] = SweepRow(case, model.make_report(
                    case.problem, run_, stats), wall + share)

        empties = groups.pop(None, [])
        for i, case, model, run_, _p, cstats, _d, wall in empties:
            stats = ProgramStats([], 0, 0, 0, 0).attach_cache(cstats)
            rows[i] = SweepRow(case, model.make_report(
                case.problem, run_, stats), wall)
        # independent signature groups serve concurrently (their scans
        # share no state; rows land at disjoint indices)
        group_items = list(groups.values())
        self.stats.batch_dispatches += len(group_items)
        self.stats.batched_cases += sum(len(g) for g in group_items)
        if self.workers > 1 and len(group_items) > 1:
            self._check_control(control, rows)
            with ThreadPoolExecutor(max_workers=self.workers) as pool:
                list(pool.map(serve_group, group_items))
        else:
            for items in group_items:
                self._check_control(control, rows)
                serve_group(items)
        return rows


def sweep(graphs: Iterable[GraphLike] = (), problems: Iterable = (),
          accelerators: Iterable[str] = ("hitgraph", "accugraph"),
          memories: Iterable[MemoryLike] = (None,),
          caches: Iterable[CacheLike] = (None,),
          variants: Iterable[Optional[str]] = (None,),
          updates: Iterable[UpdatesLike] = (None,),
          configs: Optional[Dict[str, Any]] = None,
          root: int = 0, fixed_iters: Optional[int] = None,
          backend: Optional[str] = None,
          cases: Optional[Sequence] = None,
          batch_memories: bool = False, workers: int = 1,
          devices: int = 1,
          graph_scale: float = 1.0, graph_seed: int = 0,
          sweeper: Optional[Sweeper] = None) -> List[SweepRow]:
    """Run a simulation grid; returns one row per grid point.

    Either pass the axes (``graphs x problems x accelerators x memories
    x caches x variants x updates``, expanded as an outer product in
    that order) or an explicit ``cases`` list — of :class:`SweepCase`
    and/or :class:`~repro.sim.scenario.ScenarioSpec` values — for
    irregular grids (e.g. a per-dataset config); a single
    ``ScenarioSpec`` as the first positional argument runs a one-case
    sweep.  ``updates`` sweeps the dynamic-graph mutation axis
    (``None`` = static, or :data:`~repro.graphs.updates.UPDATE_PRESETS`
    names / :class:`~repro.graphs.updates.UpdateStream` values — one
    aggregate row per dynamic case, per-epoch reports on
    ``row.epochs``).  ``graphs`` entries are :class:`Graph`
    instances or corpus preset names (``"karate"``,
    ``"powerlaw-social:degree"``, ... — see
    :data:`~repro.graphs.corpus.GRAPH_PRESETS` and
    :func:`~repro.graphs.corpus.graph_variants`); names are resolved
    through the content-addressed corpus cache at ``graph_scale`` /
    ``graph_seed``.  ``configs`` maps accelerator name -> config
    dataclass for the grid form.  ``caches`` sweeps the on-chip
    hierarchy axis (``None`` / preset names / ``"default"`` /
    :class:`~repro.core.cache.CacheConfig` — see
    :func:`repro.sim.memory.cache_variants`).  ``workers=N`` shards case
    preparation over N threads (results identical for any N; a failing
    case raises :class:`SweepError` naming it).  ``batch_memories=True``
    stacks cases whose packed programs share a compiled shape (typically
    the memory axis of one accelerator/graph point) into single
    ``vmap``-ed fused-scan dispatches; ``devices=N`` shards those
    stacked dispatches over a 1-D case mesh — rows are bit-identical for
    any (workers, devices) combination.  Pass a :class:`Sweeper` to
    share its cache/stats across calls or to inspect ``sweeper.stats``
    afterwards.
    """
    if cases is None and isinstance(graphs, ScenarioSpec):
        cases = [graphs]
    if cases is None:
        configs = configs or {}
        cases = [
            SweepCase(graph=g, problem=p, accelerator=a, memory=m,
                      cache=c, variant=v, config=configs.get(a),
                      root=root, fixed_iters=fixed_iters,
                      graph_scale=graph_scale, graph_seed=graph_seed,
                      updates=u)
            for g, p, a, m, c, v, u in itertools.product(
                graphs, problems, accelerators, memories, caches,
                variants, updates)
        ]
    else:
        cases = [c.to_case() if isinstance(c, ScenarioSpec) else c
                 for c in cases]
    if sweeper is None:
        sweeper = Sweeper(backend=backend, batch_memories=batch_memories,
                          workers=workers, devices=devices)
    else:
        if batch_memories and not sweeper.batch_memories:
            raise ValueError(
                "batch_memories=True conflicts with the provided sweeper "
                "(construct it with Sweeper(batch_memories=True))")
        if workers != 1 and workers != sweeper.workers:
            raise ValueError(
                "workers= conflicts with the provided sweeper "
                f"(it was constructed with workers={sweeper.workers})")
        if devices != 1 and devices != sweeper.devices:
            raise ValueError(
                "devices= conflicts with the provided sweeper "
                f"(it was constructed with devices={sweeper.devices})")
    return sweeper.run(cases)
