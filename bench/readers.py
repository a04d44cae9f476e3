"""Reductions the per-layer metric files (``layer_metrics/<name>.py``)
share.  Each takes the traced run's context and returns a number, or
None when the run holds nothing for it to read."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench import devtrace, roofline

# top-level decision spans of the program's obs layer: single arrivals
# (OASiS.propose), speculative bursts and their re-solves, and the
# backtrack and placement of speculative accepts
DECIDE_SPANS = ("decide", "decide_burst", "decide.resolve",
                "decide.backtrack", "decide.placement")


@dataclasses.dataclass
class Context:
    decisions: int                  # jobs decided in the window
    spans: List[Tuple[str, float, float, int]]   # name, t0_ns, t1_ns, depth
    trace: Optional[devtrace.DeviceTrace]
    device_kind: str


def spans_named(ctx: Context, names: Sequence[str]):
    return [s for s in ctx.spans if s[0] in names]


def mean_span_ms(ctx: Context, name: str) -> Optional[float]:
    sp = spans_named(ctx, (name,))
    if not sp:
        return None
    return sum(b - a for _, a, b, _ in sp) / len(sp) / 1e6


def advance_ms(ctx: Context) -> Optional[float]:
    return mean_span_ms(ctx, "stream_advance")


def commit_ms(ctx: Context) -> Optional[float]:
    return mean_span_ms(ctx, "price.commit")


def decide_ms_per_decision(ctx: Context) -> Optional[float]:
    sp = spans_named(ctx, DECIDE_SPANS)
    if not sp or not ctx.decisions:
        return None
    busy = sum(b - a for a, b in devtrace.merge((a, b) for _, a, b, _ in sp))
    return busy / ctx.decisions / 1e6


def programs_per_decision(ctx: Context) -> Optional[float]:
    if ctx.trace is None or not ctx.trace.modules or not ctx.decisions:
        return None
    lo, hi = ctx.trace.window
    n = sum(1 for e in ctx.trace.modules if lo <= e.start_ns <= hi)
    return n / ctx.decisions


def kernel_events(ctx: Context) -> List[devtrace.Event]:
    if ctx.trace is None:
        return []
    lo, hi = ctx.trace.window
    return [e for e in ctx.trace.kernel_events() if lo <= e.start_ns <= hi]


def kernel_ms_per_decision(ctx: Context) -> Optional[float]:
    ev = kernel_events(ctx)
    if not ev or not ctx.decisions:
        return None
    return sum(e.dur_ns for e in ev) / ctx.decisions / 1e6


def kernel_costs(ev: devtrace.Event) -> Optional[Dict[str, float]]:
    """Operations and bytes of one sweep launch, from its operand shapes:
    the rows ``(T, 1, dc1p)`` and the outputs ``(T, 1, d1p)``."""
    shapes = [s for s in devtrace.operand_shapes(ev) if len(s) == 3]
    if len(shapes) < 2:
        return None
    outs = [s for s in shapes if s[2] != shapes[-1][2]] or shapes
    return roofline.sweep_cost_from_shapes(shapes[-1], outs[0])


def hbm_roofline_share(ctx: Context) -> Optional[float]:
    """Least time the launches' bytes need at the HBM peak, over their
    measured time, in percent."""
    ev = kernel_events(ctx)
    if not ev:
        return None
    peak = roofline.peaks(ctx.device_kind)["hbm_bytes_per_s"]
    least = 0.0
    for e in ev:
        c = kernel_costs(e)
        if c is None:
            return None
        least += c["hbm_bytes"] / peak
    busy = sum(e.dur_ns for e in ev) / 1e9
    return 100.0 * least / busy if busy > 0 else None


def idle_share(ctx: Context) -> Optional[float]:
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
