"""Readers of the program's own stage instrumentation: the device scopes
(``jax.named_scope`` in ``core/schedule_jax.py``: ``decide.rows``,
``decide.dp``, ``decide.backtrack``, ``decide.placement``, ``price.pad``)
and the host spans at the launch boundary (``decide.prep``,
``launch.dispatch``, ``launch.fetch``).

A device op's scope is the innermost listed scope on its ``op_name``
path.  A TPU trace names each op by its HLO instruction text and keeps
``op_name`` out of the event's stats, so the paths are read from the
optimized HLO of the executables the process still holds
(``live_scope_paths``): the same instruction text, printed with its
metadata.  An op's time is its self time: its duration minus the union
of the ops nested in it (a ``while`` holds its body's ops on the same
line), so nothing is counted twice.
"""
from __future__ import annotations

import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from bench import devtrace

SCOPES = ("decide.rows", "decide.dp", "decide.backtrack",
          "decide.placement", "price.pad")
UNSCOPED = "unscoped"
# the modules whose device time the row build and the DP should cover
DECIDE_MODULES = ("jit__decide_tiled", "jit__decide_one")

_METADATA = re.compile(r', metadata=\{op_name="([^"]*)"')
_INDEX_NOTE = re.compile(r"/\*index=\d+\*/")


def instruction_key(text: str) -> str:
    """An HLO instruction's text without ``ROOT``, the printer's operand
    index notes and the attributes from ``metadata`` on: how a TPU trace
    names the op."""
    text = _INDEX_NOTE.sub("", text.strip())
    if text.startswith("ROOT "):
        text = text[5:]
    return text.split(", metadata={", 1)[0]


def scope_paths(hlo_texts: Iterable[str]) -> Dict[str, str]:
    """Instruction key -> ``op_name`` path, from HLO module texts."""
    out: Dict[str, str] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _METADATA.search(line)
            if m is not None:
                out[instruction_key(line[:m.start()])] = m.group(1)
    return out


def live_scope_paths() -> Dict[str, str]:
    """``scope_paths`` of every executable this process holds, printed
    with operand shapes as a TPU trace prints them; empty where the
    backend cannot list them."""
    import jax
    try:
        from jaxlib._jax import HloPrintOptions
        opts = HloPrintOptions()
        opts.print_operand_shape = True
        texts = [m.to_string(opts)
                 for ex in jax.devices()[0].client.live_executables()
                 for m in ex.hlo_modules()]
    except (AttributeError, ImportError, NotImplementedError, RuntimeError):
        return {}
    return scope_paths(texts)


def scope_of(path: Optional[str]) -> str:
    """Innermost listed scope on an ``op_name`` path."""
    if path:
        for part in reversed(path.split("/")):
            if part in SCOPES:
                return part
    return UNSCOPED


def self_times(ops: Sequence[devtrace.Event]) -> np.ndarray:
    """Each op's duration minus the union of the ops nested in it (ns)."""
    n = len(ops)
    start = np.fromiter((e.start_ns for e in ops), np.float64, n)
    end = start + np.fromiter((e.dur_ns for e in ops), np.float64, n)
    # by start, the longer first where two start together: an op comes
    # before the ops nested in it
    order = np.lexsort((start - end, start)).tolist()
    own = (end - start).tolist()
    s, e = start.tolist(), end.tolist()
    covered = list(s)                  # op -> end of its children so far
    stack: List[int] = []              # open ops, outermost first
    for i in order:
        a, b = s[i], e[i]
        while stack and e[stack[-1]] <= a:
            stack.pop()
        if stack:
            p = stack[-1]
            if b <= e[p]:
                lo = covered[p]
                if lo < a:
                    lo = a
                if b > lo:
                    own[p] -= b - lo
                    covered[p] = b
        stack.append(i)
    return np.asarray(own)


def scope_split(trace: devtrace.DeviceTrace,
                paths: Dict[str, str]) -> Dict[str, Dict[str, float]]:
    """Device self time (s) by scope: over the window (``all``) and over
    the ops inside ``DECIDE_MODULES`` runs (``decide``); ``decide`` also
    holds those runs' whole time under ``module``."""
    lo, hi = trace.window
    ops = [e for e in trace.ops if lo <= e.start_ns <= hi]
    own = self_times(ops) / 1e9
    names = list(SCOPES) + [UNSCOPED]
    code_of: Dict[str, int] = {}
    codes = np.empty(len(ops), np.int64)
    for i, e in enumerate(ops):
        c = code_of.get(e.name)
        if c is None:
            c = code_of[e.name] = names.index(
                scope_of(paths.get(instruction_key(e.name))))
        codes[i] = c
    mods = np.array(sorted((m.start_ns, m.end_ns) for m in trace.modules
                           if lo <= m.start_ns <= hi
                           and m.name.split("(")[0] in DECIDE_MODULES),
                    np.float64).reshape(-1, 2)
    start = np.fromiter((e.start_ns for e in ops), np.float64, len(ops))
    k = np.searchsorted(mods[:, 0], start, side="right") - 1
    inside = (k >= 0) & (start <= mods[np.maximum(k, 0), 1]) \
        if len(mods) else np.zeros(len(ops), bool)
    split: Dict[str, Dict[str, float]] = {}
    for part, keep in (("all", slice(None)), ("decide", inside)):
        tot = np.bincount(codes[keep], weights=own[keep],
                          minlength=len(names))
        cnt = np.bincount(codes[keep], minlength=len(names))
        split[part] = {n: float(t) for n, t, c in zip(names, tot, cnt) if c}
    split["decide"]["module"] = float((mods[:, 1] - mods[:, 0]).sum()) / 1e9
    return split


_cached: List[object] = [None, None]      # (trace, split) of the last run


def _split(ctx) -> Optional[Dict[str, Dict[str, float]]]:
    """The run's scope split, computed once and logged with its
    unscoped share; None without a trace or without scoped ops."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    if _cached[0] is not ctx.trace:
        split = scope_split(ctx.trace, live_scope_paths())
        _cached[:] = [ctx.trace, split]
        tot = sum(split["all"].values())
        dec = split["decide"]
        print(f"device scopes (self s): "
              + ", ".join(f"{k} {v!r}" for k, v in sorted(
                  split["all"].items()))
              + f"; unscoped share {split['all'].get(UNSCOPED, 0.0) / tot if tot else 0.0!r}"
              + f"; in {'+'.join(DECIDE_MODULES)} runs ({dec['module']!r} s):"
              + f" rows+dp {dec.get('decide.rows', 0.0) + dec.get('decide.dp', 0.0)!r} s",
              file=sys.stderr, flush=True)
    split = _cached[1]
    if not any(s in split["all"] for s in SCOPES):
        return None
    return split


def scope_ms_per_decision(ctx, scope: str) -> Optional[float]:
    split = _split(ctx)
    if split is None or not ctx.decisions:
        return None
    return split["all"].get(scope, 0.0) / ctx.decisions * 1e3


def rows_device_ms(ctx) -> Optional[float]:
    return scope_ms_per_decision(ctx, "decide.rows")


def dp_device_ms(ctx) -> Optional[float]:
    return scope_ms_per_decision(ctx, "decide.dp")


# -- host spans ------------------------------------------------------------

Span = Tuple[str, float, float, int]      # name, t0_ns, t1_ns, depth


def self_span_ns(spans: Sequence[Span], name: str) -> Optional[float]:
    """Summed time of the spans ``name`` less the union of the spans
    nested in each; None when there is none."""
    ordered = sorted(spans, key=lambda s: (s[1], -s[2]))
    total, found = 0.0, False
    for i, (n, a, b, d) in enumerate(ordered):
        if n != name:
            continue
        found = True
        inner = []
        for j in range(i + 1, len(ordered)):
            _, a2, b2, d2 = ordered[j]
            if a2 >= b:
                break
            if d2 > d and b2 <= b:
                inner.append((a2, b2))
        total += (b - a) - sum(y - x for x, y in devtrace.merge(inner))
    return total if found else None


def prep_ms(ctx) -> Optional[float]:
    ns = self_span_ns(ctx.spans, "decide.prep")
    if ns is None or not ctx.decisions:
        return None
    return ns / ctx.decisions / 1e6


def fetch_ms(ctx) -> Optional[float]:
    sp = [(a, b) for n, a, b, _ in ctx.spans if n == "launch.fetch"]
    if not sp or not ctx.decisions:
        return None
    return sum(b - a for a, b in devtrace.merge(sp)) / ctx.decisions / 1e6


def dispatches(ctx) -> Optional[float]:
    n = sum(1 for s in ctx.spans if s[0] == "launch.dispatch")
    if not n or not ctx.decisions:
        return None
    return n / ctx.decisions
