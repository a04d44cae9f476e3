"""benchmarks/run.py bench_decision schema validation (v5; v2..v4
baselines read compatibly): a malformed section must abort the write
instead of poisoning the committed baseline (it used to surface only
later, via check_regression)."""
import json

import pytest

from benchmarks.run import _merge_json, validate_tracked


def _payload():
    return {
        "schema": "bench_decision/v5",
        "platform": "test", "python": "3",
        "decision_seconds": {
            "jax": {"p50": 0.01, "p95": 0.02, "mean": 0.012},
            "loop": {"p50": 0.05, "p95": 0.3, "mean": 0.09},
            "jax_cold_mean_seconds": 0.3,
            "quick": True,
        },
        "sim_v2": {"fifo": {"v1_seconds": 1.0, "v2_seconds": 0.2,
                            "speedup": 5.0},
                   "oasis_overhead_v2_seconds": 0.1, "quick": True},
        "sim_scale": {"T": 500, "H": 100, "K": 100, "n_jobs": 2000,
                      "quick": False,
                      "wall_seconds": {"fifo": 0.4, "oasis": 650.0},
                      "utility": {"fifo": 100.0, "oasis": 7000.0},
                      "decision": {"oasis": {"p50": 0.2, "mean": 0.3,
                                             "p95": None}}},
        "serving": {"H": 50, "K": 50, "window": 64, "slots": 20000,
                    "n_jobs": 4000, "quick": False,
                    "wall_seconds": {"fifo": 2.0, "oasis": 120.0},
                    "utility": {"fifo": 900.0, "oasis": 1000.0},
                    "decisions_per_sec": {"fifo": 2000.0, "oasis": 33.0},
                    "window_bytes": {"fifo": 0, "oasis": 256000},
                    "decision": {"oasis": {"p50": 0.02, "mean": 0.03,
                                           "p95": None}}},
        "churn": {"T": 100, "H": 40, "K": 40, "n_jobs": 120,
                  "quick": False, "levels": [0.05, 0.2],
                  "wall_seconds": {"fifo": 0.02, "oasis": 20.0},
                  "utility": {"fifo": {"none": 100.0, "frac=0.05": 100.0,
                                       "frac=0.2": 90.0}},
                  "retention": {"fifo": {"frac=0.05": 1.0,
                                         "frac=0.2": 0.9}},
                  "preempted": {"fifo": {"frac=0.05": 4, "frac=0.2": 35}},
                  "preempt_dropped": {"fifo": {"frac=0.05": 0,
                                               "frac=0.2": 0}}},
        "rl": {"quick": False, "train_seconds": 250.0,
               "train_iterations": 160, "eval_seeds": [5, 6, 7],
               "instance": {"T": 100, "H": 50, "K": 50, "n_jobs": 200},
               "utility": {"learned": 500.0, "fifo": 170.0},
               "per_seed": {"learned": {"5": 900.0},
                            "fifo": {"5": 300.0}}},
        "obs": {"T": 192, "H": 10, "K": 10, "n_jobs": 64, "quick": False,
                "counters": {"decide.decisions": 64,
                             "price.device_uploads": 1},
                "derived": {"early_exit_frac": 0.4,
                            "device_uploads": 1, "preempted": 2}},
    }


def test_valid_payload_passes():
    assert validate_tracked(_payload()) == []


def test_v2_schema_still_accepted():
    """Committed v2 baselines (without the serving/churn/obs sections)
    must keep validating — the v3..v5 bumps are read-compatible."""
    p = _payload()
    p["schema"] = "bench_decision/v2"
    del p["serving"]
    del p["churn"]
    del p["obs"]
    assert validate_tracked(p) == []


def test_v3_schema_still_accepted():
    """Committed v3 baselines (without the churn/obs sections) must keep
    validating — the v4/v5 bumps are read-compatible."""
    p = _payload()
    p["schema"] = "bench_decision/v3"
    del p["churn"]
    del p["obs"]
    assert validate_tracked(p) == []


def test_v4_schema_still_accepted():
    """Committed v4 baselines (without the obs section) must keep
    validating — the v5 bump is read-compatible."""
    p = _payload()
    p["schema"] = "bench_decision/v4"
    del p["obs"]
    assert validate_tracked(p) == []


def test_wrong_schema_flagged():
    p = _payload()
    p["schema"] = "bench_decision/v1"
    assert any("schema" in x for x in validate_tracked(p))


def test_unknown_section_flagged():
    p = _payload()
    p["sim_scael"] = {"oops": 1}                  # typo'd section name
    assert any("sim_scael" in x for x in validate_tracked(p))


def test_nan_and_non_numeric_leaves_flagged():
    p = _payload()
    p["sim_scale"]["wall_seconds"]["fifo"] = float("nan")
    assert any("sim_scale.wall_seconds" in x for x in validate_tracked(p))
    p = _payload()
    p["decision_seconds"]["jax"] = {"p50": "fast"}
    assert any("decision_seconds.jax" in x for x in validate_tracked(p))
    p = _payload()
    del p["decision_seconds"]["jax"]["p95"]       # incomplete stats
    assert any("decision_seconds.jax" in x for x in validate_tracked(p))


def test_scale_dims_type_checked():
    p = _payload()
    p["sim_scale"]["T"] = "500"
    assert any("sim_scale.T" in x for x in validate_tracked(p))


def test_serving_section_checked():
    p = _payload()
    p["serving"]["window"] = "64"
    assert any("serving.window" in x for x in validate_tracked(p))
    p = _payload()
    p["serving"]["decisions_per_sec"]["oasis"] = float("inf")
    assert any("serving.decisions_per_sec" in x
               for x in validate_tracked(p))
    p = _payload()
    p["serving"]["window_bytes"] = [0]
    assert any("serving.window_bytes" in x for x in validate_tracked(p))
    p = _payload()
    p["serving"]["decision"]["oasis"] = {"p50": "slow"}
    assert any("serving.decision.oasis" in x for x in validate_tracked(p))
    p = _payload()
    p["serving_quick"] = {**p.pop("serving"), "quick": True}
    assert validate_tracked(p) == []


def test_churn_section_checked():
    p = _payload()
    p["churn"]["T"] = "100"
    assert any("churn.T" in x for x in validate_tracked(p))
    p = _payload()
    p["churn"]["levels"] = []
    assert any("churn.levels" in x for x in validate_tracked(p))
    p = _payload()
    p["churn"]["levels"] = [0.05, "lots"]
    assert any("churn.levels" in x for x in validate_tracked(p))
    p = _payload()
    p["churn"]["retention"]["fifo"]["frac=0.2"] = float("nan")
    assert any("churn.retention.fifo" in x for x in validate_tracked(p))
    p = _payload()
    p["churn"]["retention"] = [0.9]
    assert any("churn.retention" in x for x in validate_tracked(p))
    p = _payload()
    p["churn"]["preempted"]["fifo"] = 35            # not nested per-variant
    assert any("churn.preempted.fifo" in x for x in validate_tracked(p))
    p = _payload()
    p["churn_quick"] = {**p.pop("churn"), "quick": True}
    assert validate_tracked(p) == []


def test_obs_section_checked():
    p = _payload()
    p["obs"]["T"] = "192"
    assert any("obs.T" in x for x in validate_tracked(p))
    p = _payload()
    p["obs"]["quick"] = "no"
    assert any("obs.quick" in x for x in validate_tracked(p))
    p = _payload()
    p["obs"]["counters"]["decide.decisions"] = float("nan")
    assert any("obs.counters" in x for x in validate_tracked(p))
    p = _payload()
    p["obs"]["derived"] = [0.03]
    assert any("obs.derived" in x for x in validate_tracked(p))


def test_corrupted_non_dict_sections_report_instead_of_raising():
    """The baseline file on disk can be arbitrarily corrupted (that is
    the validator's whole job) — a non-dict section must come back as a
    problem, never as an AttributeError."""
    for bad in ("corrupted", [1], 3):
        for sec in ("decision_seconds", "sim_v2", "sim_scale", "serving",
                    "churn", "rl", "obs"):
            p = {"schema": "bench_decision/v5", sec: bad}
            assert any(sec in x for x in validate_tracked(p))
    p = _payload()
    p["rl"]["per_seed"] = [1]
    assert any("rl.per_seed" in x for x in validate_tracked(p))
    p = _payload()
    p["sim_scale"]["decision"] = [1]
    assert any("sim_scale.decision" in x for x in validate_tracked(p))


def test_rl_section_checked():
    p = _payload()
    p["rl"]["train_seconds"] = None
    assert any("rl.train_seconds" in x for x in validate_tracked(p))
    p = _payload()
    p["rl"]["per_seed"]["learned"]["5"] = "big"
    assert any("per_seed.learned" in x for x in validate_tracked(p))


def test_merge_json_refuses_malformed_sections(tmp_path):
    path = tmp_path / "bench.json"
    good = {"sim_scale": _payload()["sim_scale"]}
    _merge_json(str(path), good)                  # writes fine
    assert json.loads(path.read_text())["sim_scale"]["T"] == 500
    before = path.read_text()
    bad = {"sim_scale": {**_payload()["sim_scale"],
                         "wall_seconds": {"fifo": float("nan")}}}
    with pytest.raises(SystemExit):
        _merge_json(str(path), bad)
    assert path.read_text() == before             # baseline untouched


def test_merge_json_merges_and_preserves_sections(tmp_path):
    path = tmp_path / "bench.json"
    _merge_json(str(path), {"sim_scale": _payload()["sim_scale"]})
    _merge_json(str(path), {"rl": _payload()["rl"]})
    doc = json.loads(path.read_text())
    assert "sim_scale" in doc and "rl" in doc     # sections accumulate
    assert doc["schema"] == "bench_decision/v5"


def test_merge_json_upgrades_old_baselines(tmp_path):
    """Merging fresh sections into a committed v2..v4 file keeps its
    sections and rewrites the schema tag as v5."""
    path = tmp_path / "bench.json"
    v2 = _payload()
    v2["schema"] = "bench_decision/v2"
    del v2["serving"]
    del v2["churn"]
    del v2["obs"]
    path.write_text(json.dumps(v2))
    _merge_json(str(path), {"serving": _payload()["serving"]})
    doc = json.loads(path.read_text())
    assert doc["schema"] == "bench_decision/v5"
    assert "sim_scale" in doc and "serving" in doc
    _merge_json(str(path), {"obs": _payload()["obs"]})
    doc = json.loads(path.read_text())
    assert doc["schema"] == "bench_decision/v5"
    assert "serving" in doc and "obs" in doc
