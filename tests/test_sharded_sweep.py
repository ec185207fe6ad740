"""Device-count invariance of the sharded sweep executor.

``Sweeper(devices=N)`` shards batched fused-scan dispatches over a 1-D
case mesh.  The contract: sweep rows are bit-identical for ANY
(workers, devices) combination — clean AND under a chaos fault plan
(PR 7's transient-injection model, retried by the service).

The multi-device runs execute in ONE subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the flag must be
set before jax initializes, which has already happened in the test
process); the subprocess computes digests for every combination and
returns them as JSON, so the comparisons here stay readable while the
device mocking stays isolated.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import json
from repro import obs
from repro.serve import chaos
from repro.serve.engine import BreakerConfig, RetryPolicy, SimService
from repro.sim.memory import timing_variants
from repro.sim.sweep import SweepCase, Sweeper, sweep

import jax
assert len(jax.devices()) == 4, jax.devices()

# four same-geometry timing points -> ONE signature group of 4 cases,
# so devices=4 genuinely shards (one case per device)
MEMS = timing_variants(
    "ddr3", kinds=("ddr3-1066", "ddr3-1333", "ddr3-1866", "ddr4-2133"))
KW = dict(graphs=["karate"], problems=["wcc", "pr"],
          accelerators=["hitgraph"], memories=MEMS,
          batch_memories=True)


def digest(rows):
    return [(r.case.problem.value, str(r.case.memory),
             r.report.runtime_ns, r.report.total_bytes,
             r.report.row_hit_rate) for r in rows]


out = {"clean": {}, "chaos": {}, "sharded_dispatches": {},
       "lane_slots": {}}
for name, dev, wrk in (("d1", 1, 1), ("d2w2", 2, 2), ("d4", 4, 1)):
    sw = Sweeper(batch_memories=True, workers=wrk, devices=dev)
    slots = obs.counts()["serve_lane_slots"]
    out["clean"][name] = digest(sweep(**KW, sweeper=sw))
    out["lane_slots"][name] = obs.counts()["serve_lane_slots"] - slots
    out["sharded_dispatches"][name] = sw.stats.sharded_dispatches

CASES = [SweepCase("karate", p, accelerator="hitgraph", memory=m)
         for p in ("wcc", "pr") for m in MEMS]
FAST = RetryPolicy(retries=6, backoff_base_s=0.001, backoff_cap_s=0.01)
for name, dev in (("d1", 1), ("d4", 4)):
    cfg = chaos.ChaosConfig(seed=7, sites={
        "sweep.prepare": chaos.SiteConfig(rate=0.7, max_attempts=2),
        "dram.serve": chaos.SiteConfig(rate=0.5, max_attempts=1)})
    with chaos.scope(cfg):
        with SimService(batch_memories=True, devices=dev, retry=FAST,
                        breaker=BreakerConfig(threshold=10_000)) as svc:
            rows = svc.result(svc.submit(list(CASES)), timeout=240)
    out["chaos"][name] = digest(rows)

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def forced4():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("REPRO_CHAOS_SEED", None)
    env.pop("REPRO_CHAOS_SITES", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.splitlines()[-1])


class TestDeviceCountInvariance:
    def test_rows_bit_identical_across_devices(self, forced4):
        clean = forced4["clean"]
        assert clean["d4"] == clean["d1"]
        assert clean["d2w2"] == clean["d1"]

    def test_multi_device_runs_actually_sharded(self, forced4):
        assert forced4["sharded_dispatches"]["d1"] == 0
        assert forced4["sharded_dispatches"]["d4"] > 0
        assert forced4["sharded_dispatches"]["d2w2"] > 0

    def test_lane_slots_equal_across_devices(self, forced4):
        """Batches of 4 cases divide over 1, 2 and 4 devices without
        padding: the sharded dispatches run exactly the lanes of the
        single-device ones."""
        slots = forced4["lane_slots"]
        assert slots["d1"] > 0
        assert slots["d4"] == slots["d1"]
        assert slots["d2w2"] == slots["d1"]

    def test_chaos_rows_bit_identical_across_devices(self, forced4):
        """PR 7 fault plans + retries: surviving rows equal for any
        device count, and equal to the clean rows."""
        assert forced4["chaos"]["d4"] == forced4["chaos"]["d1"]
        assert forced4["chaos"]["d1"] == forced4["clean"]["d1"]


class TestShardedSweepSurface:
    def test_devices_validation(self):
        from repro.sim.sweep import Sweeper
        with pytest.raises(ValueError, match="devices"):
            Sweeper(devices=0)

    def test_facade_conflict_with_provided_sweeper(self):
        from repro.sim.sweep import Sweeper, sweep
        sw = Sweeper(devices=1)
        with pytest.raises(ValueError, match="devices"):
            sweep(graphs=["karate"], problems=["wcc"], devices=2,
                  sweeper=sw)

    def test_mesh_rejects_oversubscription(self):
        import jax
        from repro.launch.mesh import make_sweep_mesh
        with pytest.raises(ValueError, match="devices"):
            make_sweep_mesh(len(jax.devices()) + 1)
        mesh = make_sweep_mesh(1)
        assert mesh.shape["cases"] == 1
