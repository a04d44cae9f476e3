"""The reductions from a device trace and the program's spans to the
per-layer metrics, on a small synthetic trace."""
import pytest

from bench import devtrace, readers, roofline, spec

MS = 1e6  # ns


def ev(name, start_ms, dur_ms, **stats):
    return devtrace.Event(name, start_ms * MS, dur_ms * MS, stats)


KERNEL_TEXT = ("%minplus = (f32[500,1,1280]{2,1,0}, s32[500,1,1280]{2,1,0}) "
               "custom-call(f32[1,1408]{1,0} %p0, f32[500,1,128]{2,1,0} %p1)")


@pytest.fixture
def trace():
    ops = [ev("fusion.1", 10, 2), ev("fusion.2", 11, 2),      # overlap
           ev("_minplus_sweep_kernel", 20, 4, long_name=KERNEL_TEXT),
           ev("copy.3", 50, 1), ev("outside", 200, 5)]
    mods = [ev("jit__decide_one", 10, 15), ev("jit__set", 50, 1),
            ev("jit_late", 150, 1)]
    return devtrace.DeviceTrace(ops=ops, modules=mods, n_devices=1,
                                window=(0.0, 100 * MS), host_offset_ns=0.0)


def ctx(trace, decisions=4):
    spans = [("stream_advance", 0, 1 * MS, 0), ("stream_advance", 30 * MS,
                                                 32 * MS, 0),
             ("decide", 5 * MS, 25 * MS, 1),
             ("decide.backtrack", 20 * MS, 24 * MS, 2),     # inside decide
             ("price.commit", 26 * MS, 29 * MS, 1),
             ("decide_burst", 60 * MS, 70 * MS, 1)]
    return readers.Context(decisions=decisions, spans=spans, trace=trace,
                           device_kind="TPU v5 lite")


def test_busy_time_is_the_union_of_device_operations(trace):
    # [10, 13] + [20, 24] + [50, 51]; the op at 200 ms is past the window
    assert trace.busy_s() == pytest.approx(0.008)
    assert trace.window_s == pytest.approx(0.1)
    gaps = trace.idle_gaps()
    assert gaps[0] == (0.0, 10 * MS) and gaps[-1] == (51 * MS, 100 * MS)
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(0.092)


def test_device_ops_are_the_outermost_by_module(trace):
    # fusion.2 starts inside fusion.1 and counts as nested; the op at
    # 200 ms lies past the window
    assert trace.op_totals() == [
        ["jit__decide_one/_minplus_sweep_kernel", pytest.approx(0.004)],
        ["jit__decide_one/fusion.1", pytest.approx(0.002)],
        ["jit__set/copy.3", pytest.approx(0.001)]]
    assert devtrace.short_name(KERNEL_TEXT) == "minplus"


def test_idle_gaps_are_labelled_by_the_innermost_host_span(trace):
    c = ctx(trace)
    labels = dict(devtrace.label_gaps(trace.idle_gaps(), c.spans))
    # gap [0,10] mid 5 -> decide (depth 1); [13,20] mid 16.5 -> decide;
    # [24,50] mid 37 -> outside any span; [51,100] mid 75.5 -> outside
    assert labels["decide"] == pytest.approx(0.017)
    assert labels["host outside any span"] == pytest.approx(0.075)


def test_span_readers(trace):
    c = ctx(trace)
    assert readers.advance_ms(c) == pytest.approx(1.5)
    assert readers.commit_ms(c) == pytest.approx(3.0)
    # decide [5,25] holds the backtrack; decide_burst [60,70]: 30 ms / 4
    assert readers.decide_ms_per_decision(c) == pytest.approx(7.5)
    assert readers.mean_span_ms(c, "nothing") is None


def test_trace_readers(trace):
    c = ctx(trace)
    assert readers.programs_per_decision(c) == pytest.approx(2 / 4)
    assert readers.kernel_ms_per_decision(c) == pytest.approx(1.0)
    least = roofline.sweep_cost(500, 128, 1280)["hbm_bytes"] / 819e9
    assert readers.hbm_roofline_share(c) == pytest.approx(
        100 * least / 0.004)
    assert readers.idle_share(c) == pytest.approx(92.0)


def test_readers_return_nothing_without_a_trace():
    c = readers.Context(decisions=3, spans=[], trace=None,
                        device_kind="TPU v5 lite")
    for m in spec.benchmark()["per_layer"]:
        assert spec.metric_reader(m["name"])(c) is None


def test_operand_shapes_are_read_from_the_hlo_text():
    e = ev("k", 0, 1, long_name=KERNEL_TEXT)
    assert devtrace.operand_shapes(e) == [(500, 1, 1280), (500, 1, 1280),
                                          (1, 1408), (500, 1, 128)]
    assert readers.kernel_costs(e) == roofline.sweep_cost(500, 128, 1280)
