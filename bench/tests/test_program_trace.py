"""The readers of the program's stage instrumentation
(``bench/program_trace.py``) on a small synthetic trace, and the obs
spans' mirror in a real (CPU) profiler trace."""
import time

import pytest

from bench import devtrace, program_trace as pt, readers

MS = 1e6  # ns

TILED = "jit(_decide_tiled)/decide.dp"
TEXT = {  # the trace's op name -> the op_name path of its instruction
    "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b":
        TILED + "/while",
    "%fusion.1 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p), kind=kLoop":
        TILED + "/while/body/decide.rows/sort",
    "%fusion.2 = f32[8,1280]{1,0} fusion(f32[8,1280]{1,0} %q), kind=kLoop":
        TILED + "/while/body/add",
    "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop":
        TILED + "/while/body/decide.rows/cond/gather",
}
WHILE, ROWS, DP, INNER = TEXT
COPY = "%copy.1 = f32[8]{0} copy(f32[8]{0} %s)"      # no op_name: unscoped


def ev(name, start_ms, dur_ms):
    return devtrace.Event(name, start_ms * MS, dur_ms * MS, {})


@pytest.fixture
def trace():
    # the while [0, 10] holds a row op [1, 3] and a DP op [4, 7], which in
    # turn holds a row op [5, 6]; a copy runs in another module
    ops = [ev(WHILE, 0, 10), ev(ROWS, 1, 2), ev(DP, 4, 3), ev(INNER, 5, 1),
           ev(COPY, 12, 1), ev(ROWS, 200, 5)]           # the last: past it
    mods = [ev("jit__decide_tiled(7)", 0, 11), ev("jit__window_set(3)", 12,
                                                   2)]
    return devtrace.DeviceTrace(ops=ops, modules=mods, n_devices=1,
                                window=(0.0, 100 * MS), host_offset_ns=0.0)


SPANS = [("decide_burst", 0, 50 * MS, 1),
         ("decide.prep", 2 * MS, 12 * MS, 2),
         ("launch.dispatch", 4 * MS, 6 * MS, 3),
         ("launch.dispatch", 8 * MS, 9 * MS, 3),
         ("stream_advance", 11 * MS, 13 * MS, 0),       # overlaps, shallower
         ("decide.prep", 20 * MS, 21 * MS, 2),
         ("launch.dispatch", 30 * MS, 31 * MS, 2),
         ("launch.fetch", 31 * MS, 40 * MS, 2),
         ("launch.fetch", 38 * MS, 42 * MS, 3),         # overlaps the first
         ("launch.fetch", 45 * MS, 48 * MS, 2)]


def ctx(trace, spans=SPANS, decisions=4):
    return readers.Context(decisions=decisions, spans=spans, trace=trace,
                           device_kind="TPU v5 lite")


def test_self_time_leaves_out_the_ops_nested_in_an_op(trace):
    own = pt.self_times(trace.ops)
    assert (own / MS).tolist() == [5.0, 2.0, 2.0, 1.0, 1.0, 5.0]


def test_scope_split_credits_the_innermost_listed_scope(trace):
    split = pt.scope_split(trace, {pt.instruction_key(k): v
                                   for k, v in TEXT.items()})
    assert split["all"] == {"decide.dp": pytest.approx(0.007),
                            "decide.rows": pytest.approx(0.003),
                            pt.UNSCOPED: pytest.approx(0.001)}
    assert split["decide"] == {"decide.dp": pytest.approx(0.007),
                               "decide.rows": pytest.approx(0.003),
                               "module": pytest.approx(0.011)}


def test_device_scope_metrics(trace, monkeypatch):
    monkeypatch.setattr(pt, "live_scope_paths", lambda: {
        pt.instruction_key(k): v for k, v in TEXT.items()})
    c = ctx(trace)
    assert pt.rows_device_ms(c) == pytest.approx(0.75)
    assert pt.dp_device_ms(c) == pytest.approx(1.75)


def test_device_scope_metrics_are_silent_without_scopes(trace, monkeypatch):
    """A program with no stage scopes (the parent of this change) has
    nothing for them to read."""
    monkeypatch.setattr(pt, "live_scope_paths", lambda: {
        pt.instruction_key(k): "jit(_decide_tiled)/while" for k in TEXT})
    c = ctx(trace)
    assert pt.rows_device_ms(c) is None
    assert pt.dp_device_ms(c) is None


def test_host_span_metrics(trace):
    c = ctx(trace)
    # prep [2, 12] less its two dispatches (3 ms), prep [20, 21]: 8 ms / 4
    assert pt.prep_ms(c) == pytest.approx(2.0)
    # fetches [31, 42] and [45, 48]: 14 ms / 4
    assert pt.fetch_ms(c) == pytest.approx(3.5)
    assert pt.dispatches(c) == pytest.approx(0.75)


def test_host_span_metrics_are_silent_without_spans(trace):
    c = ctx(trace, spans=[("decide_burst", 0, 5 * MS, 1)])
    assert pt.prep_ms(c) is None
    assert pt.fetch_ms(c) is None
    assert pt.dispatches(c) is None


def test_scope_paths_key_instructions_as_the_trace_names_them():
    text = ('  ROOT %fusion.5 = f32[2]{0} fusion(f32[2]{0} %a, /*index=5*/'
            'f32[2]{0} %b), kind=kLoop, calls=%fc, metadata={op_name='
            '"jit(_backtrack)/decide.backtrack/min" stack_frame_id=2}, '
            'backend_config={}\n  %copy.2 = f32[2]{0} copy(f32[2]{0} %c)\n')
    paths = pt.scope_paths([text])
    key = "%fusion.5 = f32[2]{0} fusion(f32[2]{0} %a, f32[2]{0} %b), " \
          "kind=kLoop, calls=%fc"
    assert paths == {key: "jit(_backtrack)/decide.backtrack/min"}
    assert pt.scope_of(paths[key]) == "decide.backtrack"
    assert pt.scope_of("jit(f)/decide.dp/while/body/decide.rows/x") == \
        "decide.rows"
    assert pt.scope_of(None) == pt.UNSCOPED


def test_live_scope_paths_read_this_process_programs():
    import jax
    import jax.numpy as jnp

    @jax.jit
    @jax.named_scope("decide.placement")
    def f(x):
        return jnp.sin(x) * 3.0

    f(jnp.arange(5.0))
    paths = pt.live_scope_paths()
    assert any(p.startswith("jit(f)/decide.placement/")
               for p in paths.values())


def test_mirrored_spans_land_on_the_trace_clock(tmp_path):
    """Each obs span is also a host event of the same name in a profiler
    trace, and the benchmark's marker offset puts it within 100 us of its
    obs timestamp."""
    import jax
    from jax.profiler import ProfileData

    from repro import obs as obslib

    ob = obslib.Obs()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.START_MARK):
            m0 = time.perf_counter_ns()
        with obslib.activate(ob):
            with obslib.span("decide_burst", n=2):
                with obslib.span("decide.prep"):
                    time.sleep(0.001)
                with obslib.dispatch("_decide_tiled"):
                    time.sleep(0.002)
                with obslib.span("launch.fetch", program="_decide_tiled"):
                    time.sleep(0.001)
        with jax.profiler.TraceAnnotation(devtrace.END_MARK):
            m1 = time.perf_counter_ns()
    finally:
        jax.profiler.stop_trace()
    tr = devtrace.read(str(tmp_path), (m0, m1))
    names = {"decide_burst", "decide.prep", "launch.dispatch",
             "launch.fetch"}
    host = {}
    pd = ProfileData.from_file(devtrace.find_xplane(str(tmp_path)))
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    host[e.name] = (float(e.start_ns), float(e.duration_ns))
    assert set(host) == names
    for e in ob.tracer.events():
        at = ob.tracer._epoch_ns + e["ts_us"] * 1e3 + tr.host_offset_ns
        assert abs(host[e["name"]][0] - at) < 100e3, e["name"]
        assert abs(host[e["name"]][1] - e["dur_us"] * 1e3) < 100e3
