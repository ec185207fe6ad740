"""Input graphs of the benchmark.

SNAP cannot be downloaded, so a configuration's graph is a seeded
stand-in with the published vertex and edge counts: endpoints drawn from
a truncated Zipf law over a random permutation of the vertex ids.  The
seed is the configuration's own, not the run's: a dataset is one fixed
file, and a graph drawn per run would change the work of every scenario.
This is a copy of the program's ``degree_matched`` generator kept with
the benchmark, so the same seed gives the same graph as the program's
own ``instantiate(<dataset>, seed=...)``.
"""

from __future__ import annotations

import numpy as np


def seed_value(seed: int) -> int:
    """``--seed`` as a non-negative integer for NumPy's generators."""
    return int(seed) % (1 << 63)


def degree_matched(n: int, m: int, skew: float, seed: int):
    rng = np.random.default_rng(seed_value(seed))
    ranks = np.arange(1, n + 1, dtype=np.float64)
    probs = ranks ** (-skew)
    probs /= probs.sum()
    cdf = np.cumsum(probs)
    perm = rng.permutation(n)
    src = perm[np.searchsorted(cdf, rng.random(m))]
    dst = perm[np.searchsorted(cdf, rng.random(m))]
    return src.astype(np.int64), dst.astype(np.int64)


def make(spec: dict, scale: float = 1.0) -> dict:
    """The graph a configuration names: ``n``, ``src``, ``dst`` (both
    directions of every edge where the graph is undirected) and the
    name the program reports.  ``scale`` shrinks the graph for the
    benchmark's own CPU tests only."""
    n = max(int(spec["vertices"] * scale), 64)
    m = max(int(spec["edges"] * scale), 128)
    if spec["generator"] != "degree_matched":
        raise ValueError(f"unknown graph generator {spec['generator']!r}")
    src, dst = degree_matched(n, m, float(spec["skew"]), spec["seed"])
    name = spec["name"]
    if spec["undirected"]:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        name += "_undir"
    return {"n": n, "src": src, "dst": dst, "name": name}
