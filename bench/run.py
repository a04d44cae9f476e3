#!/usr/bin/env python3
"""Chip benchmark of OASiS admission (``engine.run_stream``, ``impl="jax"``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU this process finds: builds
the configuration's fleet and takes the traffic mix's job stream up to the
last slot the window of ``--seconds`` offers, its jobs in an order drawn
from ``--seed`` (the same jobs and arrival slots for every seed).
Set-up replays that whole stream unpaced on a throwaway scheduler (the
rehearsal: the run is deterministic, so it meets every program the
window will, and compiles or loads each before the window), then the
measured run replays it again: its warm-up prefix unpaced (steady
occupancy), then the window, whose slots it offers on a fixed open-loop
clock, timing each job from when it was due.  After the window the
decisions the run made are replayed against the float64 reference
(``judge.py``), which decides ``correct``.  ``--trace 1`` runs the same
window with the program's obs spans and the JAX profiler on and reports
the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object; the numbers the judge
compared, each beside its limit, are the last lines of standard error.
Exits non-zero, printing no result, when no TPU (or too few chips) is
found.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(1, SRC)

import numpy as np  # noqa: E402

from bench import devtrace, generator, judge, pacing, readers, spec  # noqa: E402

PARAMS_SAMPLE = 256         # jobs the price bounds are estimated from
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    pass


class CompileCounter:
    """Counts backend compiles and persistent-cache hits while armed."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.total_compiles = 0

    def install(self) -> None:
        import jax

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.total_compiles += 1
                if self.armed:
                    self.compiles += 1
                    self.compile_s += secs

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT and self.armed:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def setup_jax() -> str:
    """Persistent compilation cache at a fixed place in the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def find_device(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoAccelerator(f"no TPU found: first device is {d.platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"{len(devs)} devices found, the cell needs "
                            f"{chips}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def to_job(s: generator.JobSpec):
    """The program's Job for one generated record."""
    from repro.core.types import Job, SigmoidUtility
    return Job(jid=s.jid, arrival=s.arrival, epochs=s.epochs,
               num_chunks=s.num_chunks,
               minibatches_per_chunk=s.minibatches_per_chunk, tau=s.tau,
               grad_size=s.grad_size, worker_bw=s.worker_bw, ps_bw=s.ps_bw,
               worker_res=s.worker_res, ps_res=s.ps_res,
               utility=SigmoidUtility(s.gamma1, s.gamma2, s.gamma3))


def _pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class Run:
    """One run of a cell; ``execute`` returns the result line's fields."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 trace: bool, require_tpu: bool = True, log=print,
                 control: bool = False):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, trace)
        self.require_tpu, self.log = require_tpu, log
        # also judge the bfloat16 reference at the sampled decisions
        # (``bench/control.py``); the benchmark's own runs never do
        self.control = control
        self.control_numbers = None
        self.counter = CompileCounter()

    def _stream(self) -> List[generator.JobSpec]:
        """The traffic's fixed stream from its first slot through the
        window's last; a run offers these jobs in the order its seed draws
        (``generator.reordered``), so that every seed brings the same work."""
        tr = self.cell.traffic
        window_slots = int(round(self.seconds * tr["rate_jobs_per_s"]
                                 / tr["jobs_per_slot"]))
        return pacing.window_jobs(
            generator.stream(tr["stream_seed"], **tr["generator"]),
            tr["warmup_slots"], window_slots)

    def execute(self) -> dict:
        cfg, tr = self.cell.config, self.cell.traffic
        cache = setup_jax()
        device = find_device(self.cell.chips, self.require_tpu)
        import jax
        from repro import obs as obslib
        from repro.core.oasis import OASiS
        from repro.core.pricing import PriceState
        from repro.core.types import ClusterSpec
        from repro.sim import engine
        self.log(f"device: platform={device['platform']} "
                 f"kind={device['kind']} count={device['count']}")
        self.log(f"compile cache: {cache}")
        counter = self.counter
        counter.install()

        W, quantum = int(cfg["window"]), int(cfg["quantum"])
        wcaps, scaps = generator.make_fleet(cfg["H"], cfg["K"],
                                            cfg["fleet_seed"])
        cluster = ClusterSpec(T=W, worker_caps=wcaps, ps_caps=scaps)
        stream = self._stream()
        pool = generator.reordered(stream, self.seed)
        first = stream[:PARAMS_SAMPLE]
        params = engine.stream_price_params([to_job(s) for s in first],
                                            cluster, W)

        def serve(jobs, obs=None):
            return engine.run_stream(cluster, jobs, scheduler="oasis",
                                     impl="jax", window=W, quantum=quantum,
                                     params=params, check=False, obs=obs)

        self._rehearse(serve, pool)

        specs: Dict[int, generator.JobSpec] = {}
        slot_of: Dict[int, int] = {}

        def jobs():
            for s in pool:
                specs[s.jid] = s
                slot_of[s.jid] = s.arrival
                yield to_job(s)

        ob = obslib.Obs(capacity=1 << 21) if self.trace else None
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
            if self.trace else None
        marks = [0, 0]

        def window_start():
            counter.armed = True
            if ob is not None:
                ob.reset()
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                with jax.profiler.TraceAnnotation(devtrace.START_MARK):
                    marks[0] = time.perf_counter_ns()

        feed = pacing.PacedFeed(
            jobs(), warm_slots=tr["warmup_slots"],
            slot_seconds=tr["jobs_per_slot"] / tr["rate_jobs_per_s"],
            on_window_start=window_start)
        log = judge.DecisionLog(slot_of)
        self.feed = feed
        try:
            with log.installed(OASiS, PriceState):
                res = serve(feed, ob)
            feed.finish()
            counter.armed = False
            dtrace = None
            if self.trace:
                with jax.profiler.TraceAnnotation(devtrace.END_MARK):
                    marks[1] = time.perf_counter_ns()
                jax.profiler.stop_trace()
                t_read = time.perf_counter()
                dtrace = devtrace.read(trace_dir, (marks[0], marks[1]))
                self.log(f"trace: {len(dtrace.ops)} device ops, "
                         f"{len(dtrace.modules)} module runs, read in "
                         f"{time.perf_counter() - t_read!r} s")
        finally:
            if trace_dir is not None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        if feed.t0 is None:
            raise RuntimeError("the stream ended before the window began")
        setup_s = feed.t0 - _PROCESS_START
        mem = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))

        slots = feed.window_slots()
        decisions = sum(len(feed.jobs[s]) for s in slots)
        t_end = feed.done[slots[-1]]
        window_s = t_end - feed.t0
        self.log(f"window: {window_s!r} s, {len(slots)} slots with arrivals "
                 f"(slot {slots[0]} to {slots[-1]}), {decisions} decisions, "
                 f"offered {decisions / window_s!r} jobs/s")
        self.log(f"compiles in window: {counter.compiles} "
                 f"({counter.compile_s!r} s), persistent-cache loads in "
                 f"window: {counter.cache_hits}, compiles in all: "
                 f"{counter.total_compiles}")
        warm_jobs = sum(len(v) for s, v in feed.jobs.items() if s < slots[0])
        self.log(f"set-up: {setup_s!r} s (warm-up {tr['warmup_slots']} "
                 f"slots, {warm_jobs} jobs)")
        late = feed.lateness()
        back = feed.backlog()
        q = max(len(back) // 4, 1)
        growth = float(np.mean(back[-q:]) - np.mean(back[:q]))
        self.log(f"generator lateness: p50 "
                 f"{_pct(late, 50) * 1e3 if late else 0.0!r} ms, max "
                 f"{max(late) * 1e3 if late else 0.0!r} ms over "
                 f"{len(late)} slots released on time; slept "
                 f"{feed.slept!r} s")
        self.log(f"backlog (slots due, undecided): first quarter mean "
                 f"{float(np.mean(back[:q]))!r}, last quarter mean "
                 f"{float(np.mean(back[-q:]))!r}, growth {growth!r}, "
                 f"max {max(back)}")

        metrics: Dict[str, dict] = {}
        breakdown = None
        if not self.trace:
            lat = feed.latencies()
            values = {"setup_s": setup_s,
                      "admit_p50_ms": _pct(lat, 50) * 1e3,
                      "admit_p95_ms": _pct(lat, 95) * 1e3}
            self.log(f"latency: {len(lat)} jobs, p50 "
                     f"{values['admit_p50_ms']!r} ms, p95 "
                     f"{values['admit_p95_ms']!r} ms, p99 "
                     f"{_pct(lat, 99) * 1e3!r} ms, max "
                     f"{max(lat) * 1e3!r} ms")
            for m in self.cell.end_to_end:
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        else:
            ctx = self._context(ob, dtrace, feed, decisions, device["kind"])
            for m in self.cell.per_layer:
                v = spec.metric_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            device["busy_s"] = dtrace.busy_s()
            device["window_s"] = dtrace.window_s
            host = ctx.spans + [("pacing.wait", a, b, 0)
                                for a, b in self._waits(feed)]
            off = dtrace.host_offset_ns
            breakdown = {
                "device_ops": dtrace.op_totals(10),
                "idle_gaps": devtrace.label_gaps(
                    dtrace.idle_gaps(),
                    [(n, a + off, b + off, d) for n, a, b, d in host])}
            self._log_kernel(ctx)

        # the reference runs after the window, on the host, with the
        # program's device state no longer needed
        window_jids = {j for s in slots for j in feed.jobs[s]}
        sample = judge.sample_jids(sorted(window_jids), specs,
                                   int(cfg["check_sample"]), self.seed)
        t_ref = time.perf_counter()
        numbers, n_sampled, n_flips, self.control_numbers = judge.judge(
            log.calls, log.placed, specs, wcaps, scaps, W, quantum,
            window_jids, sample, res.total_utility, res.accepted, first,
            control=self.control)
        n_acc = sum(1 for j in window_jids if j in log.placed)
        self.log(f"judge: {len(window_jids)} window decisions, {n_acc} "
                 f"accepted, {n_sampled} decided again by the reference, "
                 f"{n_flips} decided differently, in "
                 f"{time.perf_counter() - t_ref!r} s")
        limits = cfg["limits"]
        out = {"correct": judge.is_correct(numbers, limits),
               "attempted": decisions,
               "failed": int(numbers["undecided"]),
               "metrics": metrics, "device": device}
        if breakdown is not None:
            out["breakdown"] = breakdown
        out["checks"] = judge.verdict_line(numbers, limits)
        return out

    def _rehearse(self, serve, pool) -> None:
        """Replay the run's jobs unpaced on a throwaway scheduler: the run
        is deterministic, so this meets, and compiles or loads, every
        program the window will meet."""
        t = time.perf_counter()
        serve([to_job(s) for s in pool])
        gc.collect()
        self.log(f"rehearsal: {len(pool)} jobs unpaced in "
                 f"{time.perf_counter() - t!r} s, "
                 f"{self.counter.total_compiles} programs compiled or loaded")

    @staticmethod
    def _waits(feed: pacing.PacedFeed):
        return [(a * 1e9, b * 1e9) for a, b in feed.waits]

    def _context(self, ob, dtrace, feed, decisions, kind):
        epoch = ob.tracer._epoch_ns
        t0_ns = feed.t0 * 1e9
        spans = []
        for ev in ob.tracer.events():
            if ev["dur_us"] is None:
                continue
            a = epoch + ev["ts_us"] * 1e3
            if a >= t0_ns:
                spans.append((ev["name"], a, a + ev["dur_us"] * 1e3,
                              ev["depth"]))
        if ob.tracer.dropped:
            self.log(f"obs ring dropped {ob.tracer.dropped} spans")
        return readers.Context(decisions=decisions, spans=spans,
                               trace=dtrace, device_kind=kind)

    def _log_kernel(self, ctx) -> None:
        ev = readers.kernel_events(ctx)
        costs = [readers.kernel_costs(e) for e in ev]
        busy = sum(e.dur_ns for e in ev) / 1e9
        if ev and busy > 0 and all(c is not None for c in costs):
            ops = sum(c["useful_ops"] for c in costs)
            byt = sum(c["hbm_bytes"] for c in costs)
            self.log(f"min-plus sweep: {len(ev)} launches, {busy!r} s, "
                     f"{ops / busy / 1e9!r} Gop/s at padded shapes, "
                     f"{byt / busy / 1e9!r} GB/s")
        else:
            self.log(f"min-plus sweep: {len(ev)} launches found in the "
                     f"trace, {busy!r} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        out = Run(cell, args.seed, args.seconds, bool(args.trace)).execute()
    except NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
