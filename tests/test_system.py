"""End-to-end behaviour of the full system: simulation pipeline, the
scheduler->trainer integration path, and the dry-run machinery (on a
small forced-device-count subprocess)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import job_from_arch, price_params_from_jobs
from repro.core.oasis import OASiS
from repro.sim import make_cluster, make_jobs, simulate


def test_simulation_all_schedulers_feasible():
    cluster = make_cluster(T=40, H=8, K=8)
    jobs = make_jobs(25, T=40, seed=2, small=True)
    for name in ["oasis", "fifo", "drf", "rrh", "dorm"]:
        r = simulate(cluster, jobs, scheduler=name, check=True)
        assert r.total_utility >= 0
        assert r.completed <= r.accepted <= len(jobs)


def test_job_from_arch_closes_the_loop():
    """Roofline terms of an arch become a schedulable Job."""
    job = job_from_arch("starcoder2-3b", arrival=0, flops_per_token=6 * 3e9,
                        param_bytes=12e9, tokens_per_step=2 ** 19,
                        target_steps=1000)
    cluster = make_cluster(T=50, H=10, K=10)
    params = price_params_from_jobs([job], cluster)
    sched = OASiS(cluster, params)
    s = sched.on_arrival(job)
    assert s is not None, "arch-derived job should be schedulable on an empty cluster"
    assert s.utility > 0


def test_oasis_decision_latency_polynomial():
    """Thm 3 practical check: decisions are sub-second at paper scale."""
    cluster = make_cluster(T=100, H=50, K=50)
    jobs = make_jobs(10, T=100, seed=4, small=False)
    r = simulate(cluster, jobs, scheduler="oasis", check=False, quantum=0)
    assert np.mean(r.decision_seconds) < 1.0, np.mean(r.decision_seconds)


@pytest.mark.slow
def test_dryrun_machinery_small_mesh():
    """lower+compile a reduced arch on forced 8-device meshes (subprocess
    because device count locks at first jax use)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax
from repro.configs import get_smoke
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
mesh_m = make_mesh((2, 2, 2), ("pod", "data", "model"))
for m in (mesh, mesh_m):
    for kind, seq, gb in [("train", 64, 8), ("decode", 64, 8)]:
        r = lower_cell(get_smoke("olmoe_1b_7b"), "t", seq, gb, kind, m)
        assert r["flops"] > 0
        assert r["collectives"]["count"] > 0
print("SUBPROCESS_OK")
"""
    env = dict(os.environ)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.join(
                             os.path.dirname(__file__), ".."), env=env,
                         timeout=600)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]


def test_sharding_rules_valid_for_all_archs():
    """Every param of every arch gets a legal sharding on the production
    mesh topology (validated structurally against a 16x16 shape)."""
    import jax
    from repro.configs import ARCHS, get_config
    from repro.models.layers import is_spec
    from repro.models.model import model_specs
    from repro.parallel.sharding import _spec_for, logical_rules

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = logical_rules(FakeMesh())
    for arch in ARCHS:
        cfg = get_config(arch)
        specs, _ = jax.tree_util.tree_flatten(model_specs(cfg),
                                              is_leaf=is_spec)
        for s in specs:
            spec = _spec_for(tuple(s.shape), tuple(s.axes), FakeMesh(), rules)
            for dim, entry in zip(s.shape, spec):
                if entry is not None:
                    axes = entry if isinstance(entry, tuple) else (entry,)
                    prod = 1
                    for a in axes:
                        prod *= FakeMesh.shape[a]
                    assert dim % prod == 0


def test_dryrun_artifacts_complete():
    """If the production sweep has been run, all 34 cells x 2 meshes exist
    and report finite numbers (skips when artifacts are absent)."""
    base = os.path.join(os.path.dirname(__file__), "..", "experiments")
    root = os.path.join(base, "final")
    if not os.path.isdir(root):
        root = os.path.join(base, "dryrun")
    if not os.path.isdir(root):
        pytest.skip("dry-run artifacts not generated")
    from repro.configs import all_cells
    files = [f for f in os.listdir(root)
             if f.endswith(".json") and not f.endswith(".probe.json")]
    if len(files) < 10:
        pytest.skip("partial dry-run")
    expected = len(all_cells()) * 2
    assert len(files) == expected, (len(files), expected)
    for f in files:
        r = json.load(open(os.path.join(root, f)))
        assert r["flops"] > 0
        assert r["memory"]["temp_bytes"] >= 0
