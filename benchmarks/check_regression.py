"""Perf-regression gate over the tracked benchmark stats.

Compares a freshly-generated ``BENCH_decision.json`` against the
committed baseline and fails (exit 1) when any recorded p50 or
wall-clock figure regressed by more than ``--ratio`` (default 2x).

Compared leaves:

* ``decision_seconds.<impl>.p50`` — per-backend decision latency
* ``sim_v2.<sched>.v2_seconds`` and the ``oasis_overhead_v2_seconds``
  figures — the event engine's wall clocks (the v1 baseline's wall
  clock is informational, not a gate)
* ``sim_scale.wall_seconds.<sched>`` and
  ``sim_scale.decision.<sched>.p50`` — the 10x-scale run (incl. the
  oasis column's per-decision latency).  The ``sim_scale_quick`` CI
  smoke record is informational only — never gated (see
  ``SCALE_SECTIONS``)
* ``serving.wall_seconds.<sched>``, ``serving.decision.<sched>.p50``
  and — inverted, since higher is better — the sustained
  ``serving.decisions_per_sec.<sched>`` throughput of the continuous
  serving mode (gate fires when baseline/fresh exceeds the ratio, i.e.
  throughput dropped).  ``serving_quick`` is the CI smoke — never gated
  (see ``SERVING_SECTIONS``)
* ``minplus.<case>.p50`` — the structure-aware DP slot kernel
  micro-bench (chain vs monotone dispatch vs plateau); like the
  decision sections, a ``quick`` flag mismatch between baseline and
  fresh refuses the check.  The ``sim_scale``/``serving``
  ``decision.stages`` sub-record (per-stage profiling wall) is
  diagnostic and never gated
* ``churn.retention.<sched>.<variant>`` — utility retention under fleet
  churn, also inverted (higher is better): the gate fires when a
  scheduler keeps a ``ratio``-times smaller share of its churn-free
  utility than the baseline recorded.  ``churn_quick`` is the CI smoke
  — never gated (see ``CHURN_SECTIONS``)
* ``obs.derived.*`` — the flight-recorder probe's deterministic
  efficiency figures (schema v5): ``early_exit_frac`` and
  ``device_uploads`` gate lower-is-better; a drift here is a semantic
  efficiency regression (the early exit stopped firing, full-table
  uploads reappeared) even when wall clocks stay within ratio

A section is only ever compared against a like-configured baseline
(``quick`` flag for the decision sections; T/H/K/n_jobs dims for the
scale sections).  A configuration mismatch is an **error** (exit 2):
silently diffing a quick run against a full-mode baseline — or vice
versa — compares different workloads and means the caller's setup is
wrong.  Pass ``--allow-config-mismatch`` to downgrade mismatched
sections to a reported skip.  Improvements and sections missing from
one side are reported but never fail the gate.

Usage::

    python -m benchmarks.check_regression BASELINE FRESH [--ratio 2.0]
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Iterator, Tuple

# baseline figures below this are treated as noise and skipped: a 2x
# ratio on a sub-millisecond wall clock is scheduler jitter, not a
# regression
MIN_BASELINE_SECONDS = 1e-3


# gated scale sections.  sim_scale_quick is deliberately NOT gated: it is
# the CI smoke (shrunk instance, jit-compile-heavy, ~90x p50/p95 in-run
# spread) regenerated on shared runners against a dev-machine baseline —
# a 2x wall-clock ratio there measures runner weather, not regressions.
# Its record is still written and uploaded for inspection.
SCALE_SECTIONS = ("sim_scale", "sim_scale_100x")

# gated serving sections.  serving_quick is the CI smoke (short streamed
# trace on shared runners) — informational only, same rationale as
# sim_scale_quick.
SERVING_SECTIONS = ("serving",)

# gated churn sections.  churn_quick is the CI smoke — informational
# only, same rationale as sim_scale_quick.  Retention is deterministic
# (seeded trace, seeded workload), so unlike the wall-clock leaves a
# drop here is a semantic robustness regression, not runner weather.
CHURN_SECTIONS = ("churn",)

# gated flight-recorder sections (schema v5): the obs probe's derived
# efficiency figures are deterministic counter ratios, so like churn
# retention a drift is semantic — the early exit stopped firing, or
# full-table uploads reappeared on the commit path.  ``early_exit_frac``
# / ``device_uploads`` are lower-is-better leaves.  ``preempted`` and
# the raw counter
# snapshot are informational — preemption counts track the churn
# workload, not an efficiency property.
OBS_SECTIONS = ("obs",)

# the gated derived leaves of the obs section (lower is better)
OBS_LEAVES = ("early_exit_frac", "device_uploads")


def _leaves(doc: dict) -> Iterator[Tuple[str, float]]:
    """Yield (path, value) for every gated numeric leaf in ``doc``."""
    dec = doc.get("decision_seconds", {})
    for impl, stats in sorted(dec.items()):
        if isinstance(stats, dict) and "p50" in stats:
            yield f"decision_seconds.{impl}.p50", float(stats["p50"])
    sim = doc.get("sim_v2", {})
    for key, stats in sorted(sim.items()):
        if isinstance(stats, dict) and "v2_seconds" in stats:
            yield f"sim_v2.{key}.v2_seconds", float(stats["v2_seconds"])
        elif key.endswith("_v2_seconds") and isinstance(stats, (int, float)):
            yield f"sim_v2.{key}", float(stats)
    for section in SCALE_SECTIONS + SERVING_SECTIONS:
        scale = doc.get(section, {})
        for sched, wall in sorted(scale.get("wall_seconds", {}).items()):
            yield f"{section}.wall_seconds.{sched}", float(wall)
        for sched, stats in sorted(scale.get("decision", {}).items()):
            if sched == "stages":
                continue        # diagnostic sub-record, never gated
            if isinstance(stats, dict) and stats.get("p50") is not None:
                yield f"{section}.decision.{sched}.p50", float(stats["p50"])
    mp = doc.get("minplus", {})
    for case, stats in sorted(mp.items()):
        if isinstance(stats, dict) and stats.get("p50") is not None:
            yield f"minplus.{case}.p50", float(stats["p50"])
    for section in OBS_SECTIONS:
        derived = doc.get(section, {}).get("derived", {})
        for name in OBS_LEAVES:
            if name in derived:
                yield f"{section}.derived.{name}", float(derived[name])


def _rate_leaves(doc: dict) -> Iterator[Tuple[str, float]]:
    """Yield (path, value) for the gated HIGHER-is-better leaves
    (sustained throughputs); the gate inverts the ratio for these."""
    for section in SERVING_SECTIONS:
        srv = doc.get(section, {})
        for sched, dps in sorted(srv.get("decisions_per_sec", {}).items()):
            yield f"{section}.decisions_per_sec.{sched}", float(dps)
    for section in CHURN_SECTIONS:
        ch = doc.get(section, {})
        for sched, per_variant in sorted(ch.get("retention", {}).items()):
            if not isinstance(per_variant, dict):
                continue
            for variant, ret in sorted(per_variant.items()):
                yield f"{section}.retention.{sched}.{variant}", float(ret)


def _section_quick(doc: dict, section: str):
    """Per-section quick flag (v2 schema), falling back to the v1
    top-level flag for old baselines."""
    sec = doc.get(section, {})
    if isinstance(sec, dict) and "quick" in sec:
        return bool(sec["quick"])
    return bool(doc.get("quick"))


def _config_mismatches(base: dict, fresh: dict) -> Dict[str, str]:
    """Section prefixes whose configurations differ.

    Comparing such leaves would diff different workloads (e.g. a
    ``--quick`` fresh run against a full-mode baseline): the caller
    decides whether that refuses the whole check (default) or merely
    skips the section (``--allow-config-mismatch``)."""
    skip: Dict[str, str] = {}
    for section in ("decision_seconds", "sim_v2", "minplus"):
        if not (base.get(section) and fresh.get(section)):
            continue            # missing on one side: MISS leaves, no refusal
        bq, fq = _section_quick(base, section), _section_quick(fresh, section)
        if bq != fq:
            skip[f"{section}."] = (
                f"quick flag differs (baseline={bq}, fresh={fq})")
    dim_sets = {section: ("T", "H", "K", "n_jobs", "quick")
                for section in SCALE_SECTIONS}
    dim_sets.update({section: ("H", "K", "window", "slots", "n_jobs",
                               "quick") for section in SERVING_SECTIONS})
    dim_sets.update({section: ("T", "H", "K", "n_jobs", "levels", "quick")
                     for section in CHURN_SECTIONS})
    dim_sets.update({section: ("T", "H", "K", "n_jobs", "quick")
                     for section in OBS_SECTIONS})
    for section, dims in dim_sets.items():
        bs, fs = base.get(section, {}), fresh.get(section, {})
        if bs and fs and any(bs.get(d) != fs.get(d) for d in dims):
            skip[f"{section}."] = (
                "dims differ (baseline "
                + "/".join(str(bs.get(d)) for d in dims) + " vs fresh "
                + "/".join(str(fs.get(d)) for d in dims) + ")")
    return skip


def check(base: dict, fresh: dict, ratio: float,
          allow_config_mismatch: bool = False) -> int:
    mismatched = _config_mismatches(base, fresh)
    if mismatched and not allow_config_mismatch:
        print("configuration mismatch between baseline and fresh run — "
              "refusing to diff different workloads:")
        for prefix, why in sorted(mismatched.items()):
            print(f"  {prefix}*: {why}")
        print("(re-run both sides with the same mode, or pass "
              "--allow-config-mismatch to skip the mismatched sections)")
        return 2
    fresh_leaves = dict(_leaves(fresh))
    failures = []
    compared = 0
    for path, bval in _leaves(base):
        skipped = next((why for pre, why in mismatched.items()
                        if path.startswith(pre)), None)
        if skipped is not None:
            print(f"SKIP  {path}: {skipped}")
            continue
        if path not in fresh_leaves:
            print(f"MISS  {path}: not in fresh run (not gated)")
            continue
        if bval < MIN_BASELINE_SECONDS:
            print(f"SKIP  {path}: baseline {bval:.2e}s below noise floor")
            continue
        fval = fresh_leaves[path]
        r = fval / bval
        compared += 1
        mark = "FAIL" if r > ratio else "ok  "
        print(f"{mark}  {path}: {bval:.4f}s -> {fval:.4f}s ({r:.2f}x)")
        if r > ratio:
            failures.append((path, r))
    # higher-is-better leaves (throughputs): invert the ratio so the gate
    # still fires on "r > ratio" when the fresh figure DROPPED
    fresh_rates = dict(_rate_leaves(fresh))
    for path, bval in _rate_leaves(base):
        skipped = next((why for pre, why in mismatched.items()
                        if path.startswith(pre)), None)
        if skipped is not None:
            print(f"SKIP  {path}: {skipped}")
            continue
        if path not in fresh_rates:
            print(f"MISS  {path}: not in fresh run (not gated)")
            continue
        if bval <= 0.0 or 1.0 / bval < MIN_BASELINE_SECONDS:
            # a baseline sustaining >1k decisions/sec spends sub-ms per
            # decision — same noise floor as the latency leaves
            print(f"SKIP  {path}: baseline {bval:.1f} below noise floor")
            continue
        fval = fresh_rates[path]
        r = bval / max(fval, 1e-12)
        compared += 1
        mark = "FAIL" if r > ratio else "ok  "
        print(f"{mark}  {path}: {bval:.4g} -> {fval:.4g} "
              f"({r:.2f}x drop)")
        if r > ratio:
            failures.append((path, r))
    if failures:
        print(f"\n{len(failures)} regression(s) beyond {ratio:.1f}x:")
        for path, r in failures:
            print(f"  {path}: {r:.2f}x")
        return 1
    print(f"\nno regressions beyond {ratio:.1f}x ({compared} figures compared)")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("baseline", help="committed BENCH_decision.json")
    ap.add_argument("fresh", help="freshly generated BENCH_decision.json")
    ap.add_argument("--ratio", type=float, default=2.0,
                    help="fail when fresh/baseline exceeds this (default 2)")
    ap.add_argument("--allow-config-mismatch", action="store_true",
                    help="skip (instead of refuse on) sections whose "
                         "configuration differs between the two files")
    args = ap.parse_args()
    with open(args.baseline) as fh:
        base = json.load(fh)
    with open(args.fresh) as fh:
        fresh = json.load(fh)
    sys.exit(check(base, fresh, args.ratio, args.allow_config_mismatch))


if __name__ == "__main__":
    main()
