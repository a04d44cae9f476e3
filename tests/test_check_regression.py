"""benchmarks/check_regression gate: leaf extraction for the scale,
serving, and churn sections (incl. the inverted higher-is-better
throughput/retention leaves), and the hard refusal on quick-vs-full
configuration mismatches (PR 4)."""
from benchmarks.check_regression import _leaves, _rate_leaves, check


def _doc(quick_dec=True, scale_T=500, oasis_p50=0.2, fifo_wall=1.0,
         quick_scale=False, serving_window=64, oasis_dps=40.0,
         serving_wall=100.0, oasis_ret=0.8, churn_levels=(0.05, 0.2)):
    return {
        "schema": "bench_decision/v4",
        "decision_seconds": {"jax": {"p50": 0.01}, "quick": quick_dec},
        "sim_scale": {
            "T": scale_T, "H": 100, "K": 100, "n_jobs": 2000,
            "quick": quick_scale,
            "wall_seconds": {"fifo": fifo_wall, "oasis": 600.0},
            "decision": {"oasis": {"p50": oasis_p50, "mean": 0.3}},
        },
        "serving": {
            "H": 50, "K": 50, "window": serving_window, "slots": 20000,
            "n_jobs": 4000, "quick": False,
            "wall_seconds": {"fifo": 2.0, "oasis": serving_wall},
            "decisions_per_sec": {"fifo": 2000.0, "oasis": oasis_dps},
            "window_bytes": {"fifo": 0, "oasis": 256000},
            "decision": {"oasis": {"p50": 0.02, "mean": 0.03}},
        },
        "churn": {
            "T": 100, "H": 40, "K": 40, "n_jobs": 120, "quick": False,
            "levels": list(churn_levels),
            "wall_seconds": {"fifo": 0.02, "oasis": 20.0},
            "utility": {"fifo": {"none": 100.0, "frac=0.2": 90.0},
                        "oasis": {"none": 200.0, "frac=0.2": 160.0}},
            "retention": {"fifo": {"frac=0.2": 0.9},
                          "oasis": {"frac=0.2": oasis_ret}},
            "preempted": {"fifo": {"frac=0.2": 35},
                          "oasis": {"frac=0.2": 55}},
            "preempt_dropped": {"fifo": {"frac=0.2": 0},
                                "oasis": {"frac=0.2": 7}},
        },
    }


def test_leaves_include_scale_decision_p50():
    paths = dict(_leaves(_doc()))
    assert paths["sim_scale.wall_seconds.oasis"] == 600.0
    assert paths["sim_scale.decision.oasis.p50"] == 0.2
    assert "sim_scale.decision.oasis.mean" not in paths   # p50 is the gate


def test_serving_leaves_and_rate_leaves():
    paths = dict(_leaves(_doc()))
    assert paths["serving.wall_seconds.oasis"] == 100.0
    assert paths["serving.decision.oasis.p50"] == 0.02
    # throughputs are higher-is-better: extracted separately, not as
    # lower-better wall leaves
    assert not any("decisions_per_sec" in p for p in paths)
    rates = dict(_rate_leaves(_doc()))
    assert rates == {"serving.decisions_per_sec.fifo": 2000.0,
                     "serving.decisions_per_sec.oasis": 40.0,
                     "churn.retention.fifo.frac=0.2": 0.9,
                     "churn.retention.oasis.frac=0.2": 0.8}


def test_serving_throughput_drop_gates_inverted():
    """The gate fires when throughput DROPPED by more than the ratio —
    and never when it improved."""
    base = _doc()
    slower = _doc(oasis_dps=10.0)                 # 4x throughput drop
    assert check(base, slower, ratio=2.0) == 1
    faster = _doc(oasis_dps=400.0)                # 10x improvement: fine
    assert check(base, faster, ratio=2.0) == 0
    # fifo sustains >1k/s (sub-ms per decision): below the noise floor,
    # its throughput column is never gated
    noisy = _doc()
    noisy["serving"]["decisions_per_sec"]["fifo"] = 1.0
    assert check(base, noisy, ratio=2.0) == 0


def test_serving_wall_regression_gates():
    assert check(_doc(), _doc(serving_wall=450.0), ratio=2.0) == 1


def test_churn_retention_drop_gates_inverted():
    """Retention is higher-is-better: the gate fires when a scheduler
    keeps a ratio-times smaller share of its churn-free utility than
    the baseline — and never when retention improved."""
    base = _doc()
    collapsed = _doc(oasis_ret=0.3)               # 0.8 -> 0.3: >2x drop
    assert check(base, collapsed, ratio=2.0) == 1
    better = _doc(oasis_ret=1.0)                  # improvement: fine
    assert check(base, better, ratio=2.0) == 0
    # churn retention never appears among the lower-is-better leaves
    assert not any("retention" in p for p in dict(_leaves(base)))


def test_churn_levels_mismatch_refuses():
    base, fresh = _doc(), _doc(churn_levels=(0.05, 0.5))
    assert check(base, fresh, ratio=2.0) == 2
    assert check(base, fresh, ratio=2.0, allow_config_mismatch=True) == 0


def test_churn_quick_section_never_gated():
    base, fresh = _doc(), _doc()
    base["churn_quick"] = {**base["churn"], "quick": True}
    fresh["churn_quick"] = {**fresh["churn"], "quick": True,
                            "retention": {"oasis": {"frac=0.2": 0.01}}}
    assert check(base, fresh, ratio=2.0) == 0


def test_v3_baseline_without_churn_not_gated():
    """Diffing a fresh v4 run against a committed v3 baseline (no churn
    section) must neither refuse nor gate the new retention leaves."""
    base = _doc()
    del base["churn"]
    base["schema"] = "bench_decision/v3"
    assert check(base, _doc(oasis_ret=0.01), ratio=2.0) == 0


def test_serving_dims_mismatch_refuses():
    base, fresh = _doc(), _doc(serving_window=32)
    assert check(base, fresh, ratio=2.0) == 2
    assert check(base, fresh, ratio=2.0, allow_config_mismatch=True) == 0


def test_serving_quick_section_never_gated():
    base, fresh = _doc(), _doc()
    fresh["serving_quick"] = {**fresh["serving"], "quick": True,
                              "wall_seconds": {"oasis": 9999.0}}
    base["serving_quick"] = {**base["serving"], "quick": True}
    assert check(base, fresh, ratio=2.0) == 0


def test_v2_baseline_without_serving_not_gated():
    """Diffing a fresh v3 run against a committed v2 baseline (no serving
    section) must neither refuse nor gate the new leaves."""
    base = _doc()
    del base["serving"]
    base["schema"] = "bench_decision/v2"
    assert check(base, _doc(oasis_dps=1.0), ratio=2.0) == 0


def test_matching_configs_compare_and_gate():
    base, fresh = _doc(), _doc(oasis_p50=0.25, fifo_wall=1.5)
    assert check(base, fresh, ratio=2.0) == 0
    worse = _doc(oasis_p50=0.9)                            # 4.5x regression
    assert check(base, worse, ratio=2.0) == 1


def test_quick_flag_mismatch_refuses():
    """A quick fresh section must never be silently diffed against a
    full-mode baseline: the gate refuses (exit 2) unless explicitly
    downgraded to a skip."""
    base, fresh = _doc(quick_dec=False), _doc(quick_dec=True)
    assert check(base, fresh, ratio=2.0) == 2
    assert check(fresh, base, ratio=2.0) == 2              # and vice versa
    assert check(base, fresh, ratio=2.0, allow_config_mismatch=True) == 0


def _with_minplus(doc, quick, chain_p50=0.006):
    doc["minplus"] = {"quick": quick,
                      "chain_dc64": {"p50": chain_p50},
                      "plateau_stair_dc64": {"p50": 0.002}}
    return doc


def test_minplus_leaves_gated():
    base = _with_minplus(_doc(), quick=False)
    paths = dict(_leaves(base))
    assert paths["minplus.chain_dc64.p50"] == 0.006
    assert paths["minplus.plateau_stair_dc64.p50"] == 0.002
    worse = _with_minplus(_doc(), quick=False, chain_p50=0.1)  # 16x slower
    assert check(base, worse, ratio=2.0) == 1
    assert check(base, _with_minplus(_doc(), quick=False), ratio=2.0) == 0


def test_minplus_quick_mismatch_refuses():
    """The minplus micro-bench measures different shapes in --quick mode:
    diffing quick against full must refuse (exit 2), not silently
    compare different workloads."""
    base = _with_minplus(_doc(), quick=False)
    fresh = _with_minplus(_doc(), quick=True)
    assert check(base, fresh, ratio=2.0) == 2
    assert check(fresh, base, ratio=2.0) == 2              # and vice versa
    assert check(base, fresh, ratio=2.0, allow_config_mismatch=True) == 0


def test_decision_stages_subrecord_never_gated():
    """The per-stage profiling sub-record rides inside decision sections
    as diagnostics: it must produce no gated leaves and regressing it
    must not fire the gate."""
    base, fresh = _doc(), _doc()
    base["sim_scale"]["decision"]["stages"] = {
        "row_build": 1.0, "dp_sweep": 2.0, "backtrack": 0.1,
        "placement": 0.1, "decisions": 100.0}
    fresh["sim_scale"]["decision"]["stages"] = {
        "row_build": 900.0, "dp_sweep": 900.0, "backtrack": 900.0,
        "placement": 900.0, "decisions": 100.0}
    assert not any("stages" in p for p in dict(_leaves(base)))
    assert check(base, fresh, ratio=2.0) == 0


def test_scale_dims_mismatch_refuses():
    base, fresh = _doc(), _doc(scale_T=150, quick_scale=True)
    assert check(base, fresh, ratio=2.0) == 2
    assert check(base, fresh, ratio=2.0, allow_config_mismatch=True) == 0


def test_missing_sections_are_not_gated():
    base = _doc()
    fresh = {"schema": "bench_decision/v2",
             "decision_seconds": {"jax": {"p50": 0.01}, "quick": True}}
    assert check(base, fresh, ratio=2.0) == 0


def test_section_missing_entirely_does_not_phantom_refuse():
    """A fresh file from e.g. `--only simscale` has no decision_seconds
    section at all; the quick-flag refusal must not fire on the fallback
    quick=False of the absent section — missing sections are reported as
    MISS, never a config mismatch."""
    base = _doc(quick_dec=True)
    fresh = {"schema": "bench_decision/v2", "sim_scale": _doc()["sim_scale"]}
    assert check(base, fresh, ratio=2.0) == 0
    assert check(fresh, base, ratio=2.0) == 0


def _with_obs(doc, quick=False, obs_T=192, early=0.4, uploads=1):
    doc["obs"] = {"T": obs_T, "H": 10, "K": 10, "n_jobs": 64,
                  "quick": quick,
                  "counters": {"decide.decisions": 64.0,
                               "engine.preemptions": 2.0},
                  "derived": {"early_exit_frac": early,
                              "device_uploads": uploads,
                              "preempted": 2.0}}
    return doc


def test_obs_leaves_split_by_direction():
    """The flight-recorder derived figures: early_exit_frac and
    device_uploads gate lower-is-better, none inverted; preempted and the
    raw counters are informational — no leaves."""
    doc = _with_obs(_doc())
    paths = dict(_leaves(doc))
    assert paths["obs.derived.early_exit_frac"] == 0.4
    assert paths["obs.derived.device_uploads"] == 1
    rates = dict(_rate_leaves(doc))
    assert not any(p.startswith("obs.") for p in rates)
    every = {**paths, **rates}
    assert not any("preempted" in p for p in every)
    assert not any("counters" in p for p in every)


def test_obs_efficiency_regression_gates():
    base = _with_obs(_doc())
    # early exit stopped firing (0.4 -> 0.95 of the horizon visited)
    assert check(base, _with_obs(_doc(), early=0.95), ratio=2.0) == 1
    # full-table uploads reappeared on the commit path
    assert check(base, _with_obs(_doc(), uploads=64), ratio=2.0) == 1
    assert check(base, _with_obs(_doc()), ratio=2.0) == 0


def test_obs_dims_mismatch_refuses():
    base, fresh = _with_obs(_doc()), _with_obs(_doc(), quick=True, obs_T=48)
    assert check(base, fresh, ratio=2.0) == 2
    assert check(base, fresh, ratio=2.0, allow_config_mismatch=True) == 0


def test_v4_baseline_without_obs_not_gated():
    """Diffing a fresh v5 run against a committed v4 baseline (no obs
    section) must neither refuse nor gate the new derived leaves."""
    base = _doc()
    base["schema"] = "bench_decision/v4"
    assert check(base, _with_obs(_doc(), early=0.99), ratio=2.0) == 0
