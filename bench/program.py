"""What the benchmark takes from the program: the system under test
(``Sweeper``, the engine under ``sweep()`` and ``SimService``), its
counters and, through the trace, its program names.  Scenario dicts
become the program's ``SweepCase`` values here and nowhere else."""

from __future__ import annotations

import math

from repro.core import vectorized as vec
from repro.core.cache import CacheConfig
from repro.core.dram import DRAMConfig, DRAMOrganization, DRAMTiming
from repro.graphs.formats import Graph
from repro.sim.registry import get_accelerator
from repro.sim.sweep import SweepCase, Sweeper

#: the dispatch counters of the serve paths (``dispatch_counts`` keys)
SERVE_DISPATCHES = ("pallas", "fused", "fused_batch")


def program_graph(graph: dict) -> Graph:
    return Graph(graph["n"], graph["src"], graph["dst"], directed=False,
                 name=graph["name"])


def design_fields(design: dict, n: int) -> dict:
    """The design with a partition count resolved to a size, as the
    program's ``PartitionPolicy(count=...)`` resolves it."""
    d = dict(design)
    parts = d.pop("partitions", None)
    if parts is not None:
        d["partition_elements"] = max(math.ceil(n / int(parts)), 1)
    return d


def dram_config(mem: dict) -> DRAMConfig:
    return DRAMConfig(
        name=mem["name"], standard=mem["standard"],
        channels=mem["channels"], timing=DRAMTiming(**mem["timing"]),
        org=DRAMOrganization(ranks=mem["ranks"], banks=mem["banks"],
                             rows=mem["rows"], row_bytes=mem["row_bytes"]),
        clock_ghz=mem["clock_ghz"], order=tuple(mem["order"]))


def sweep_case(g: Graph, config: dict, scenario: dict) -> SweepCase:
    spec = get_accelerator(config["accelerator"])
    cache = scenario["cache"]
    return SweepCase(
        graph=g, problem=config["problem"],
        accelerator=config["accelerator"],
        memory=dram_config(scenario["memory"]),
        cache=CacheConfig(**cache) if cache else None,
        config=spec.config_cls(**design_fields(scenario["design"], g.n)),
        fixed_iters=config["fixed_iters"])


def make_sweeper(traffic: dict) -> Sweeper:
    return Sweeper(**traffic["sweeper"])


def dispatch_counts() -> dict:
    return vec.dispatch_counts()


def report_fields(report) -> dict:
    """A ``SimReport`` in the reference's form."""
    return {
        "system": report.system, "problem": report.problem,
        "runtime_ns": report.runtime_ns, "iterations": report.iterations,
        "edges": report.edges, "vertices": report.vertices,
        "total_requests": report.total_requests,
        "total_bytes": report.total_bytes,
        "row_hit_rate": report.row_hit_rate,
        "cache_lookups": report.cache_lookups,
        "cache_hits": report.cache_hits,
        "prefetch_hits": report.prefetch_hits,
        "phases": [[p.name, p.requests, p.bytes, p.start_cycle,
                    p.end_cycle, p.row_hits, p.row_conflicts]
                   for p in report.phases],
    }


def algorithm_values(sweeper: Sweeper, case: SweepCase):
    """The algorithm result the sweeper computed for ``case`` (read from
    its session's cache; nothing is run again)."""
    sess = sweeper._session(case.graph)
    spec = get_accelerator(case.accelerator)
    run = sess.algorithm_run(spec, case.problem, case.config, case.root,
                             case.fixed_iters)
    return run.values
