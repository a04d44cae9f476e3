"""Flight-recorder observability layer (repro.obs).

Pins the four contracts the subsystem makes:

* disabled (the default) is a true no-op — runs are bit-identical with
  and without an installed recorder, nothing is emitted while no
  recorder is active, and the disabled hot-path helpers are cheap;
* enabled runs record a well-formed trace: Chrome-trace export carries
  the required fields, spans nest, the ring bounds memory with explicit
  drop accounting;
* the metrics registry round-trips snapshot()/reset() and validates;
* the engine integration (`run(..., obs=)` / `run_stream(..., obs=)`)
  populates the documented span/counter catalog and restores the
  module-global disabled state on return.
"""
import json
import re
import time

import numpy as np
import pytest

from repro import obs as obslib
from repro.obs.metrics import Histogram, Registry
from repro.obs.trace import NULL_SPAN, Tracer
from repro.sim import engine
from repro.sim.fleet import make_fleet_trace
from repro.sim.workload import make_cluster, make_jobs


@pytest.fixture(autouse=True)
def _no_leak():
    """Every test must leave the process-global recorder uninstalled."""
    yield
    assert obslib.ENABLED is False
    assert obslib.current() is None


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_spans_nest_and_record_duration():
    tr = Tracer()
    with tr.span("outer", jid=1):
        with tr.span("inner"):
            time.sleep(0.001)
    evs = list(tr.events())
    # inner exits (and records) first
    assert [e["name"] for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["dur_us"] >= inner["dur_us"] > 0
    # inner lies within outer's window
    assert outer["ts_us"] <= inner["ts_us"]
    assert (inner["ts_us"] + inner["dur_us"]
            <= outer["ts_us"] + outer["dur_us"])
    assert outer["args"] == {"jid": 1}


def test_span_set_merges_attrs():
    tr = Tracer()
    with tr.span("s", a=1) as sp:
        sp.set(b=2)
    (ev,) = tr.events()
    assert ev["args"] == {"a": 1, "b": 2}


def test_ring_bounds_memory_and_counts_drops():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["s6", "s7", "s8", "s9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_chrome_export_schema(tmp_path):
    tr = Tracer()
    with tr.span("decide", jid=7, impl="jax"):
        with tr.span("dp_sweep", arr=np.arange(3)):   # non-scalar arg
            pass
    tr.instant("jax.compile", seconds=0.5)
    path = tmp_path / "trace.json"
    n = tr.export_chrome(str(path), metrics={"counters": {"x": 1}})
    assert n == 3
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert doc["metrics"] == {"counters": {"x": 1}}
    evs = doc["traceEvents"]
    assert len(evs) == 3
    for ev in evs:
        assert set(ev) >= {"name", "cat", "ph", "ts", "pid", "tid"}
        assert ev["cat"] == "repro"
        assert isinstance(ev["ts"], (int, float))
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(complete) == 2 and len(instants) == 1
    for ev in complete:
        assert ev["dur"] >= 0
    assert instants[0]["s"] == "t"
    # args must be JSON scalars (non-scalars stringified)
    for ev in evs:
        for v in ev.get("args", {}).values():
            assert isinstance(v, (int, float, bool, str, type(None)))
    # nesting well-formed: child window inside parent window
    by_name = {e["name"]: e for e in complete}
    parent, child = by_name["decide"], by_name["dp_sweep"]
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-6


def test_jsonl_export_round_trips(tmp_path):
    tr = Tracer()
    with tr.span("a", k="v"):
        pass
    path = tmp_path / "t.jsonl"
    assert tr.export_jsonl(str(path)) == 1
    (line,) = path.read_text().splitlines()
    ev = json.loads(line)
    assert ev["name"] == "a" and ev["args"] == {"k": "v"}


def test_dropped_events_recorded_in_chrome_export(tmp_path):
    tr = Tracer(capacity=2)
    for i in range(5):
        tr.instant(f"e{i}")
    path = tmp_path / "trace.json"
    tr.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert doc["otherData"] == {"dropped_events": 3}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counters_gauges_histograms_snapshot_roundtrip():
    reg = Registry()
    reg.inc("a")
    reg.inc("a", 2)
    reg.set_gauge("g", 0.5)
    reg.observe("h", 0.002)
    reg.observe("h", 5.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"a": 3}
    assert snap["gauges"] == {"g": 0.5}
    h = snap["histograms"]["h"]
    assert h["count"] == 2
    assert h["sum"] == pytest.approx(5.002)
    assert sum(h["counts"]) == 2
    assert len(h["counts"]) == len(h["edges"]) + 1   # +Inf overflow
    # snapshot is a deep copy: mutating it does not touch the registry
    snap["counters"]["a"] = 99
    assert reg.snapshot()["counters"]["a"] == 3
    reg.reset()
    empty = reg.snapshot()
    assert empty == {"counters": {}, "gauges": {}, "histograms": {}}


def test_histogram_buckets_cover_range():
    h = Histogram(edges=(0.1, 1.0))
    for v in (0.05, 0.5, 50.0):
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 3
    assert d["counts"] == [1, 1, 1]        # <=0.1, (0.1,1.0], +Inf
    assert d["sum"] == pytest.approx(50.55)
    with pytest.raises(ValueError):
        Histogram(edges=(1.0, 0.1))        # unsorted edges refused


def test_registry_validate_flags_non_finite():
    reg = Registry()
    reg.inc("ok")
    assert reg.validate() == []
    reg.set_gauge("bad", float("nan"))
    assert any("bad" in p for p in reg.validate())


# ---------------------------------------------------------------------------
# activation + disabled-mode contract
# ---------------------------------------------------------------------------

def test_disabled_helpers_are_noops():
    assert obslib.span("x") is NULL_SPAN
    with obslib.span("x") as sp:
        sp.set(a=1)                         # must not raise
    obslib.inc("c")
    obslib.observe("h", 1.0)
    obslib.set_gauge("g", 1.0)
    obslib.event("e")
    assert obslib.current() is None and obslib.ENABLED is False


def test_activate_scopes_and_restores():
    ob = obslib.Obs()
    with obslib.activate(ob):
        assert obslib.ENABLED and obslib.current() is ob
        obslib.inc("k")
        inner = obslib.Obs()
        with obslib.activate(inner):        # nested install
            assert obslib.current() is inner
        assert obslib.current() is ob       # restored, still enabled
        assert obslib.ENABLED
    assert obslib.ENABLED is False and obslib.current() is None
    assert ob.metrics.snapshot()["counters"] == {"k": 1}
    # activate(None) is a passthrough that changes nothing
    with obslib.activate(None) as got:
        assert got is None and obslib.ENABLED is False


def test_enable_disable_process_global():
    ob = obslib.enable()
    try:
        assert obslib.ENABLED and obslib.current() is ob
        obslib.inc("n")
    finally:
        obslib.disable()
    assert ob.metrics.snapshot()["counters"] == {"n": 1}


def test_disabled_overhead_micro_pin():
    """The disabled fast path must stay allocation-free and cheap: one
    module-global read per emission.  Pinned loosely (50x a float add)
    so real regressions (dict lookups, object churn) fail while CI
    scheduler noise does not."""
    N = 20000
    t0 = time.perf_counter()
    acc = 0.0
    for _ in range(N):
        acc += 1.0
    base = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(N):
        obslib.inc("c")
        obslib.span("s")
    cost = time.perf_counter() - t0
    assert cost < max(50 * base, 0.05), (cost, base)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _instance(T=24, HK=3, n=8):
    cluster = make_cluster(T=T, H=HK, K=HK)
    return cluster, make_jobs(n, T=T, seed=0, small=True)


def test_enabled_run_bit_identical_and_emits_catalog():
    cluster, jobs = _instance()
    r0 = engine.run(cluster, jobs, scheduler="oasis", impl="fast")
    ob = obslib.Obs()
    r1 = engine.run(cluster, jobs, scheduler="oasis", impl="fast", obs=ob)
    assert r0.summary() == r1.summary()
    assert r0.completion == r1.completion
    c = ob.metrics.snapshot()["counters"]
    assert c["decide.decisions"] == r1.accepted + (r1.n_jobs - r1.accepted)
    assert c["engine.arrivals"] == r1.n_jobs
    assert c["price.commits"] == r1.accepted
    names = {e["name"] for e in ob.tracer.events()}
    assert {"decide", "price.commit"} <= names
    hist = ob.metrics.snapshot()["histograms"]["decide.seconds"]
    assert hist["count"] == c["decide.decisions"]


def test_disabled_run_emits_nothing():
    cluster, jobs = _instance()
    ob = obslib.Obs()
    with obslib.activate(ob):
        pass                                # installed, but no run inside
    engine.run(cluster, jobs, scheduler="oasis", impl="fast")
    assert len(ob.tracer) == 0
    assert ob.metrics.snapshot()["counters"] == {}


def test_reactive_run_records_repack_and_ffwd():
    cluster, jobs = _instance()
    ob = obslib.Obs()
    r = engine.run(cluster, jobs, scheduler="drf", obs=ob)
    c = ob.metrics.snapshot()["counters"]
    assert c["engine.completions"] == r.completed
    assert c["engine.ffwd_slots"] >= 1
    names = {e["name"] for e in ob.tracer.events()}
    assert {"repack", "ffwd"} <= names
    # satellite: reactive repack wall time is the per-decision latency
    assert len(r.decision_seconds) >= 1
    assert all(d >= 0 for d in r.decision_seconds)


def test_churn_run_records_preemptions_and_live_frac():
    # bigger instance than the default: enough live jobs that a seeded
    # failure actually lands on one (the bench obs probe's quick dims)
    cluster, jobs = _instance(T=48, HK=6, n=24)
    fleet = make_fleet_trace(cluster, seed=1, mtbf=cluster.T / 1.6,
                             mttr=cluster.T / 12)
    ob = obslib.Obs()
    r = engine.run(cluster, jobs, scheduler="dorm", fleet=fleet, obs=ob)
    c = ob.metrics.snapshot()["counters"]
    assert c.get("engine.preemptions", 0) == r.preempted > 0
    assert "churn_step" in {e["name"] for e in ob.tracer.events()}
    s = r.summary()
    assert s["preempted"] == r.preempted
    assert s["preempt_dropped"] == r.preempt_dropped
    assert 0.0 < s["live_frac"] <= 1.0
    # churn-free runs report a fully-live fleet
    assert engine.run(cluster, jobs,
                      scheduler="dorm").summary()["live_frac"] == 1.0


def test_stream_run_bit_identical_and_counts():
    cluster, jobs = _instance()
    r0 = engine.run_stream(cluster, iter(jobs), scheduler="oasis",
                           impl="fast")
    ob = obslib.Obs()
    r1 = engine.run_stream(cluster, iter(jobs), scheduler="oasis",
                           impl="fast", obs=ob)
    assert r0.summary() == r1.summary()
    c = ob.metrics.snapshot()["counters"]
    assert c["engine.arrivals"] == r1.n_jobs
    assert c["price.window_advances"] >= 1
    assert "stream_advance" in {e["name"] for e in ob.tracer.events()}


def test_obs_export_embeds_metrics(tmp_path):
    cluster, jobs = _instance()
    ob = obslib.Obs()
    engine.run(cluster, jobs, scheduler="oasis", impl="fast", obs=ob)
    path = tmp_path / "run.json"
    n = ob.export_chrome(str(path))
    doc = json.loads(path.read_text())
    assert len(doc["traceEvents"]) == n > 0
    assert doc["metrics"]["counters"]["decide.decisions"] >= 1


# ---------------------------------------------------------------------------
# launch boundary, parent ids, profiler mirror, compile instants
# ---------------------------------------------------------------------------

# spans that belong to one job carry its id
_PER_JOB = ("decide", "decide.resolve", "decide.backtrack",
            "decide.placement", "price.commit")


def test_jax_stream_run_records_the_launch_boundary():
    cluster, jobs = _instance(T=48, HK=6, n=24)
    jobs = sorted(jobs, key=lambda j: j.arrival)
    r0 = engine.run_stream(cluster, iter(jobs), scheduler="oasis",
                           impl="jax", window=48)
    ob = obslib.Obs()
    r1 = engine.run_stream(cluster, iter(jobs), scheduler="oasis",
                           impl="jax", window=48, obs=ob)
    assert r0.summary() == r1.summary()
    assert r0.completion == r1.completion
    evs = list(ob.tracer.events())
    names = {e["name"] for e in evs}
    assert {"decide.prep", "launch.dispatch", "launch.fetch",
            "decide_burst", "decide.dp_sweep", "price.commit"} <= names
    by_id = {e["id"]: e for e in evs}
    assert len(by_id) == len(evs)
    for e in evs:
        p = e["parent"]
        if p is None:
            assert e["depth"] == 0
            continue
        par = by_id[p]
        assert e["depth"] == par["depth"] + 1
        end = e["ts_us"] + (e["dur_us"] or 0.0)
        assert par["ts_us"] <= e["ts_us"]
        assert end <= par["ts_us"] + par["dur_us"] + 1e-3
    for e in evs:
        if e["name"] in _PER_JOB:
            assert "jid" in e["args"], e
        if e["name"] == "arrival_burst":
            assert "t" in e["args"]
        if e["name"] in ("launch.dispatch", "launch.fetch"):
            assert e["args"]["program"]
    dispatch = [e for e in evs if e["name"] == "launch.dispatch"]
    c = ob.metrics.snapshot()["counters"]
    assert c["decide.launches"] == len(dispatch)
    programs = {e["args"]["program"] for e in dispatch}
    assert {"_decide_tiled", "_backtrack", "_place_slots",
            "_window_set"} <= programs
    # every blocking read-back of a decide launch sits in a fetch span
    fetched = {e["args"]["program"] for e in evs
               if e["name"] == "launch.fetch"}
    assert {"_decide_tiled", "_backtrack", "_place_slots"} <= fetched


def test_single_arrival_kernel_path_records_its_launch():
    """The TPU's single-arrival path (``use_pallas=True``; interpret mode
    here): prep, one ``_decide_one`` launch and one fetch."""
    from repro.core.pricing import PriceState, price_params_from_jobs
    from repro.core.schedule_jax import best_schedule_fused
    cluster = make_cluster(T=10, H=3, K=3)
    jobs = make_jobs(2, T=10, seed=1, small=True)
    state = PriceState(cluster, price_params_from_jobs(jobs, cluster))
    want = best_schedule_fused(jobs[0], state, use_pallas=True)
    ob = obslib.Obs()
    with obslib.activate(ob):
        got = best_schedule_fused(jobs[0], state, use_pallas=True)
    assert (got is None) == (want is None)
    if want is not None:
        assert got.finish == want.finish and got.workers.keys() == \
            want.workers.keys()
    evs = list(ob.tracer.events())
    prog = [(e["name"], e["args"]["program"]) for e in evs
            if e["name"] in ("launch.dispatch", "launch.fetch")]
    assert prog == [("launch.dispatch", "_decide_one"),
                    ("launch.fetch", "_decide_one")]
    assert [e["args"]["jid"] for e in evs
            if e["name"] == "decide.prep"] == [jobs[0].jid]
    assert ob.metrics.counter("decide.launches") == 1


def test_disabled_jax_run_emits_nothing():
    cluster, jobs = _instance()
    ob = obslib.Obs()
    engine.run(cluster, jobs, scheduler="oasis", impl="jax")
    assert len(ob.tracer) == 0
    assert ob.metrics.snapshot()["counters"] == {}


def test_instants_and_spans_carry_ids():
    tr = Tracer()
    with tr.span("a") as a:
        tr.instant("mark", k=1)
        with tr.span("b"):
            pass
    mark, b, a_ev = list(tr.events())
    assert a_ev["parent"] is None and a_ev["id"] == a._id
    assert mark["parent"] == a_ev["id"] and b["parent"] == a_ev["id"]
    assert len({mark["id"], b["id"], a_ev["id"]}) == 3
    (ev,) = [e for e in tr.chrome_events() if e["name"] == "b"]
    assert ev["args"]["parent_id"] == a_ev["id"]


def test_spans_open_trace_annotations_only_under_a_recorder():
    opened = []

    class Note:
        def __init__(self, name, **kw):
            opened.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    tr = Tracer(annotate=Note)
    with tr.span("launch.dispatch", program="_decide_tiled", arr=[1]):
        pass
    assert opened == [("launch.dispatch",
                       {"program": "_decide_tiled", "arr": "[1]"})]
    assert obslib.span("x") is NULL_SPAN
    assert obslib.dispatch("_decide_tiled") is NULL_SPAN


def test_fresh_compile_lands_under_the_span_that_triggered_it():
    import jax
    k = float(time.perf_counter_ns() % 1000003)     # a program not seen
    f = jax.jit(lambda x: x * k + 1.0)
    x = np.arange(7, dtype=np.float32)
    f(x + 1)                       # compiled with no recorder: not counted
    g = jax.jit(lambda x: x * k - 1.0)
    ob = obslib.Obs()
    with obslib.activate(ob):
        with obslib.span("outer") as outer:
            g(x)
        g(x)                       # cached: no compile
        f(x)
    comp = [e for e in ob.tracer.events() if e["name"] == "jax.compile"]
    assert len(comp) == 1
    assert comp[0]["parent"] == outer._id
    assert comp[0]["args"]["seconds"] > 0
    assert ob.metrics.counter("jax.compiles") == 1


def _scope_cases():
    """Each decide program at a tiny shape, with the scopes it holds."""
    import jax
    import jax.numpy as jnp
    from repro.core import schedule_jax as sj
    from repro.core.types import R
    T, H, K, M, D1, B, WA = 64, 3, 3, 64, 128, 2, 8
    f32, i32 = jnp.float32, jnp.int32

    def s(*shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    state = (s(T, H, R), s(T, K, R), s(H, R), s(K, R), s(R), s(R), s(), s())
    padded = state + (s(T, R), s(T, H, R), s(T, K, R))
    tiled_jd = (s(B, 2 * R + 2), s(B, 2, M, dtype=i32), s(B, T), s(B, T),
                s(B, 4, dtype=i32), s(B))
    one_jd = (s(2 * R + 2), s(2, M, dtype=i32), s(T), s(3, dtype=i32))
    return {
        "_decide_tiled": (lambda: sj._decide_tiled.lower(
            padded, tiled_jd, T=T, d1=D1, mono=0),
            ("decide.rows", "decide.dp")),
        "_decide_one": (lambda: sj._decide_one.lower(
            state, one_jd, d1=D1, use_pallas=False),
            ("decide.rows", "decide.dp", "decide.backtrack",
             "decide.placement")),
        "_backtrack": (lambda: sj._backtrack.lower(
            s(T, M), s(T, D1), s(dtype=i32), s(dtype=i32), s(dtype=i32)),
            ("decide.backtrack",)),
        "_place_slots": (lambda: sj._place_slots.lower(
            state, s(2 * R + 2), s(WA), s(WA), s(WA, dtype=i32), wa=WA),
            ("decide.placement",)),
        "_pad_state": (lambda: sj._pad_state.lower(*state, T_pad=T),
                       ("price.pad",)),
        "_pad_patch": (lambda: sj._pad_patch.lower(
            *padded[:2], padded[8], *padded[9:], *state,
            s(dtype=i32), span=8), ("price.pad",)),
    }


@pytest.mark.parametrize("program", ["_decide_tiled", "_decide_one",
                                     "_backtrack", "_place_slots",
                                     "_pad_state", "_pad_patch"])
def test_decide_programs_carry_their_device_scopes(program):
    lower, scopes = _scope_cases()[program]
    text = lower().compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(f"jit({program})/" in p and f"/{scope}/" in p + "/"
                   for p in paths), (program, scope)
    # no scope name may match the sweep kernel's trace pattern
    assert not any("minplus" in s for s in scopes)


def test_cached_programs_are_keyed_on_their_scopes():
    """A program loaded from the persistent compilation cache keeps the
    stage scopes of the source that compiled it, so the engine keys the
    cache on metadata: a program compiled before the scopes existed
    cannot stand in for one that has them."""
    import jax

    import repro.core.schedule_jax  # noqa: F401

    assert jax.config.jax_compilation_cache_include_metadata_in_key
