"""The benchmark's inputs: its copy of the generator draws what the
program's generator draws, cells load by name, and the peak table and the
sweep's operation count hold."""
import itertools
import json
import os
import re

import numpy as np
import pytest

from bench import generator, readers, roofline, spec

ROOT = spec.ROOT


SINGLE = dict(rate=0.2, diurnal_period=288, diurnal_amp=0.6,
              burst_prob=0.01, burst_mean_len=12, burst_tail=1.5,
              burst_cap=8.0, small=False)


def _mix(name):
    return spec._load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                        name + ".json"))["generator"]


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
@pytest.mark.parametrize("mix", ["serving", "burst.paced"])
def test_generator_draws_the_programs_jobs(seed, mix):
    """The committed mix, and the serving stream's bursts and diurnal
    swing, which take every branch of the generator."""
    from repro.sim.workload import stream_jobs
    gen = SINGLE if mix == "serving" else _mix(mix)
    mine = list(itertools.islice(generator.stream(seed, **gen), 300))
    theirs = list(itertools.islice(stream_jobs(seed=seed, **gen), 300))
    assert len(mine) == len(theirs) == 300
    for a, b in zip(mine, theirs):
        assert (a.jid, a.arrival, a.epochs, a.num_chunks,
                a.minibatches_per_chunk) == (b.jid, b.arrival, b.epochs,
                                             b.num_chunks,
                                             b.minibatches_per_chunk)
        assert (a.tau, a.grad_size, a.worker_bw, a.ps_bw) == (
            b.tau, b.grad_size, b.worker_bw, b.ps_bw)
        assert np.array_equal(a.worker_res, b.worker_res)
        assert np.array_equal(a.ps_res, b.ps_res)
        u = b.utility
        assert (a.gamma1, a.gamma2, a.gamma3) == (u.gamma1, u.gamma2,
                                                  u.gamma3)


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
def test_reordered_keeps_the_jobs_and_their_slots(seed):
    jobs = list(itertools.islice(generator.stream(3, rate=0.8), 200))
    mine = generator.reordered(jobs, seed)
    assert [j.arrival for j in mine] == [j.arrival for j in jobs]
    assert [j.jid for j in mine] == list(range(len(jobs)))
    assert sorted(j.gamma1 for j in mine) == sorted(j.gamma1 for j in jobs)
    assert [j.gamma1 for j in mine] != [j.gamma1 for j in jobs]
    assert [j.gamma1 for j in generator.reordered(jobs, seed)] == [
        j.gamma1 for j in mine]


def test_fleet_is_the_programs_cluster():
    from repro.sim.workload import make_cluster
    w, s = generator.make_fleet(100, 100, 0)
    c = make_cluster(T=500, H=100, K=100)
    assert np.array_equal(w, c.worker_caps) and np.array_equal(s, c.ps_caps)


def test_every_cell_loads_with_its_files():
    bm = spec.benchmark()
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["window"] >= 1
        assert set(cell.config["limits"]) >= {"flip_margin", "payoff_gap",
                                              "capacity_excess"}
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        assert cell.traffic["rate_jobs_per_s"] > 0
        assert cell.traffic["warmup_slots"] >= 0
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell")


def test_every_metric_has_a_reader():
    for m in spec.benchmark()["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_file_keeps_its_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bm = json.load(fh)
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bm["workloads"]}
    configs = {c["name"] for c in bm["configs"]}
    assert {w["config"] for w in bm["workloads"]} == configs
    assert len({(w["config"], w["traffic"]) for w in bm["workloads"]}) \
        == len(cells)
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200
    for c in bm["configs"]:
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(ROOT, c["file"]))
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        for c in m.get("workloads", cells):
            assert c in e2e[m["moves"]].get("workloads", cells)


def test_peak_table_is_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert roofline.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_sweep_cost_against_a_hand_count():
    # T=500 slots, 65 taps, 1201 columns: lanes pad to 128 and 1280
    c = roofline.sweep_cost(500, 65, 1201)
    assert c["useful_ops"] == 2 * 500 * 65 * 1201 == 78_065_000
    # carry (1, 1408) once + rows 500 x 128 + cost and argmin 500 x 1280
    words = 1408 + 500 * 128 + 2 * 500 * 1280
    assert words == 1_345_408
    assert c["hbm_bytes"] == 4 * words
    assert roofline.sweep_cost_from_shapes((500, 1, 128), (500, 1, 1280)) \
        == roofline.sweep_cost(500, 128, 1280)
    assert readers  # the readers module imports cleanly

