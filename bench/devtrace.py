"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operations, XLA module executions, the min-plus
kernel's events, busy time, and idle gaps labelled by what the host was
doing.

The window is marked in the trace by two ``TraceAnnotation`` events that
the harness emits at its start and end (``bench.window_start`` /
``bench.window_end``); their perf-counter readings put the program's
``obs`` spans on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

START_MARK = "bench.window_start"
END_MARK = "bench.window_end"
KERNEL_PATTERN = re.compile(r"minplus", re.IGNORECASE)
SHAPE = re.compile(r"(f32|s32|bf16|f64|s64|u32|pred)\[([0-9,]*)\]")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event]                   # device operations, all chips
    modules: List[Event]               # XLA module executions, all chips
    n_devices: int
    window: Tuple[float, float]        # trace-clock ns of the markers
    host_offset_ns: float              # trace_ns = perf_counter_ns + offset

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def kernel_events(self) -> List[Event]:
        return [e for e in self.ops if KERNEL_PATTERN.search(e.name)
                or KERNEL_PATTERN.search(str(e.stats.get("long_name", "")))]

    def module_of(self) -> Dict[int, str]:
        """Index of each op -> the name of the module run it lies in."""
        mods = sorted((m.start_ns, m.end_ns, m.name.split("(")[0])
                      for m in self.modules)
        starts = [m[0] for m in mods]
        out = {}
        for i, e in enumerate(self.ops):
            k = bisect.bisect_right(starts, e.start_ns) - 1
            if k >= 0 and e.start_ns <= mods[k][1]:
                out[i] = mods[k][2]
        return out

    def busy_intervals(self) -> List[Tuple[float, float]]:
        return merge((e.start_ns, e.end_ns) for e in self.ops)

    def busy_s(self) -> float:
        """Union of device-operation time in the window, averaged over
        devices."""
        lo, hi = self.window
        tot = sum(max(0.0, min(b, hi) - max(a, lo))
                  for a, b in self.busy_intervals())
        return tot / 1e9 / max(self.n_devices, 1)

    def idle_gaps(self) -> List[Tuple[float, float]]:
        lo, hi = self.window
        gaps, cur = [], lo
        for a, b in self.busy_intervals():
            if b <= lo or a >= hi:
                continue
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if hi > cur:
            gaps.append((cur, hi))
        return gaps

    def op_totals(self, top: int = 10) -> List[List]:
        """Device seconds of the outermost operations in the window, by
        module and short HLO name (``jit__decide_one/while.60``), largest
        first."""
        lo, hi = self.window
        mod = self.module_of()
        tot: Dict[str, float] = {}
        end = -1.0
        for i in sorted(range(len(self.ops)),
                        key=lambda i: (self.ops[i].start_ns,
                                       -self.ops[i].dur_ns)):
            e = self.ops[i]
            if e.start_ns < end or not lo <= e.start_ns <= hi:
                continue                # nested in an op already counted
            end = e.end_ns
            key = f"{mod.get(i, '?')}/{short_name(e.name)}"
            tot[key] = tot.get(key, 0.0) + e.dur_ns / 1e9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:top]]


def short_name(hlo: str) -> str:
    """``%while.60 = (...) while(...)`` -> ``while.60``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()[:64]


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read(log_dir: str, marks_perf_ns: Tuple[int, int]) -> DeviceTrace:
    """Device events and window markers from the newest trace in
    ``log_dir``; ``marks_perf_ns`` are the perf-counter readings taken at
    the two markers."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(find_xplane(log_dir))
    ops: List[Event] = []
    modules: List[Event] = []
    marks: Dict[str, float] = {}
    devices = 0
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            devices += 1
            for line in plane.lines:
                lname = line.name
                if lname == "XLA Ops":
                    dest = ops
                elif lname == "XLA Modules":
                    dest = modules
                else:
                    continue
                for ev in line.events:
                    dest.append(Event(ev.name, float(ev.start_ns),
                                      float(ev.duration_ns), _stats(ev)))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (START_MARK, END_MARK):
                        marks[ev.name] = float(ev.start_ns)
    if START_MARK not in marks or END_MARK not in marks:
        raise ValueError("window markers missing from the trace")
    offset = marks[START_MARK] - marks_perf_ns[0]
    return DeviceTrace(ops=ops, modules=modules, n_devices=devices,
                       window=(marks[START_MARK], marks[END_MARK]),
                       host_offset_ns=offset)


def operand_shapes(ev: Event) -> List[Tuple[int, ...]]:
    """Array shapes named in an event's HLO text (its name on a TPU
    trace, or its ``long_name`` stat), outputs first, in order."""
    text = ev.name + " " + str(ev.stats.get("long_name", ""))
    return [tuple(int(x) for x in dims.split(",") if x)
            for _, dims in SHAPE.findall(text)]


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Tuple[str, float, float, int]],
               top: int = 10) -> List[List]:
    """Sum idle-gap seconds by the innermost host span (name, start, end,
    depth; trace clock) covering each gap's midpoint."""
    spans = sorted(host_spans, key=lambda s: s[1])
    tot: Dict[str, float] = {}
    active: List[Tuple[str, float, float, int]] = []
    i = 0
    for a, b in sorted(gaps):
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][1] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] >= mid]
        label = max(active, key=lambda s: s[3])[0] if active \
            else "host outside any span"
        tot[label] = tot.get(label, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:top]]
