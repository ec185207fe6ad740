"""The one traffic generator: turns a traffic file and a configuration
into the calls a run sends, from ``--seed``.

A scenario is plain data: ``key`` (unique within a run), ``design``
(the accelerator's fields), ``design_key`` (equal for scenarios that
share a request program), ``memory`` (the memory block of the
configuration, its ``timing`` possibly changed) and ``cache`` (``None``
or the on-chip level's fields).  A call is a list of scenarios.  The
first call of every run is the warm-up; the window sends the rest in
order, and no scenario appears twice.  The seed draws the order and
the variants, never the amount of work: every seed gets the same sizes.
"""

from __future__ import annotations

import copy
import json

import numpy as np

from bench.graphs import seed_value

TIMING_FIELDS = ("tCL", "tRCD", "tRP", "tRAS", "tBL", "tRRD", "tFAW")


def _key(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def timing_grid(config: dict, traffic: dict, seed: int):
    """Calls of one batch of the traffic's grades each, every timing
    parameter moved by an offset drawn from ``offsets``, clamped at
    ``min_cycles``; a vector that came before is drawn again."""
    rng = np.random.default_rng([seed_value(seed), 1])
    offsets = np.asarray(traffic["offsets"], dtype=np.int64)
    design = dict(config["design"])
    design_key = _key([design, config["cache"]])
    seen = set()
    while True:
        call = []
        for grade in traffic["grades"]:
            base = np.array([grade["timing"][f] for f in TIMING_FIELDS])
            while True:
                vec = np.maximum(base + rng.choice(offsets, len(base)),
                                 traffic["min_cycles"])
                if tuple(vec) not in seen:
                    seen.add(tuple(vec))
                    break
            memory = copy.deepcopy(config["memory"])
            memory["timing"] = dict(zip(TIMING_FIELDS, map(int, vec)))
            memory["name"] = f"{config['memory']['name']}@{grade['name']}"
            call.append({"key": _key([design_key, memory["timing"]]),
                         "design_key": design_key, "design": design,
                         "memory": memory, "cache": config["cache"]})
        yield call


def _design_point(config: dict, point: dict, cache_name: str) -> dict:
    design = dict(config["design"])
    design.update(point)
    if "partitions" in point:
        design.pop("partition_elements", None)
    cache = config["design_points"]["caches"][cache_name]
    return {"key": _key([design, cache]), "design_key": _key([design, cache]),
            "design": design, "memory": copy.deepcopy(config["memory"]),
            "cache": cache}


def design_points(config: dict, traffic: dict, seed: int):
    """Designs of the configuration's design space, visited by cost class:
    the traffic's ``classes`` fix the axes that set a design's size, in a
    fixed order, and the seed draws the ``seeded`` axis within each class
    (every value once per class), so every seed runs the same sizes.
    Each window call serves one design under each of ``window_caches``;
    the warm-up serves the first ``warm_designs`` designs under
    ``warm_cache``, which gives the same compiled shapes (a cache shapes
    issue cycles only) and answers no scenario of the window."""
    dims = config["design_points"]["dimensions"]
    axis = traffic["seeded"]
    rng = np.random.default_rng([seed_value(seed), 2])
    draws = [[dims[axis][i] for i in rng.permutation(len(dims[axis]))]
             for _ in traffic["classes"]]
    designs = [dict(cls, **{axis: draws[c][r]})
               for r in range(len(dims[axis]))
               for c, cls in enumerate(traffic["classes"])]
    for d in designs:
        if any(d[k] not in dims[k] for k in d):
            raise ValueError(f"design {d} is outside the design space")
    yield [_design_point(config, d, traffic["warm_cache"])
           for d in designs[:int(traffic["warm_designs"])]]
    for d in designs:
        yield [_design_point(config, d, c) for c in traffic["window_caches"]]


GENERATORS = {"timing_grid": timing_grid, "design_points": design_points}


def calls(config: dict, traffic: dict, seed: int):
    """Iterator over the calls of a run; the first is the warm-up."""
    return GENERATORS[traffic["generator"]](config, traffic, seed)
