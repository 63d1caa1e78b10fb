"""One benchmark run: a cell of `BENCHMARK.json`, one seed, one window.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: fail unless JAX's first device is a TPU and there are as many
chips as the cell asks for; keep JAX's persistent compilation cache in
`<checkout>/.jax_cache`; warm up every shape the cell's mix can draw
(set-up); measure for `--seconds` (requests sent before the deadline run
to completion); compare what the window produced with the plain
reference; print the result as the last line of standard output and
each compared number beside its limit as the last lines of standard
error.

Everything that belongs to one configuration, mix or metric is found by
name: `configs/<config>.json` (sizes) beside `configs/<config>.py` (its
reference check and its lower-precision control), `traffic/<mix>.json`
(read by `lib/traffic.py`, sent by `lib/drivers.py`), and
`metrics/<metric>.py` (a reader: `read(run) -> float | None`, with the
spans it needs in `SPANS`). With `--trace 0` the line carries the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics, read from a
profiler trace of the window, from host spans around the program's
entry points, and from the program's own spans and counters
(`repro.core.trace`, recorded while traced and handed to readers as
`RunData.recording`).
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

from bench.lib import drivers as drivers_mod
from bench.lib import traffic as traffic_mod
from bench.lib.spans import Spans

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


@dataclass
class RunData:
    """What a metric reader sees of one run."""
    config: dict
    records: list
    window_s: float
    setup_s: float
    spans: Spans
    trace: Optional[object] = None
    peaks: Optional[dict] = None
    traced: list = field(default_factory=list)   # completed while traced
    # the program's spans and counters inside the requests completed
    # while traced (None: untraced, or a program without its own spans)
    recording: Optional[object] = None

    @property
    def done(self):
        return [r for r in self.records if r.ok]


class Traced:
    """The traced part of a window: the profiler, the host spans and the
    program's own recording run from the window's start until the first
    completion `seconds` later (a mix's `trace_seconds`; a trace of the
    fused transient engine grows by some 100 MB a second), wrapped in a
    `bench.window` annotation."""

    def __init__(self, log_dir: str, spans: Spans, seconds: float,
                 on: bool):
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation
        self.log_dir, self.spans, self.limit = log_dir, spans, seconds
        self.on, self.n_done, self.seconds = on, 0, 0.0
        self.t_end = -math.inf
        self.recording, self._recording = None, None
        if not on:
            return
        try:
            from repro.core import trace as program_trace
        except ImportError:                 # a program without its spans
            program_trace = None
        if program_trace is not None:
            self._recording = program_trace.recording()
            self.recording = self._recording.__enter__()
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        self.annotation = TraceAnnotation("bench.window")
        self.annotation.__enter__()
        spans.active = True
        self.t0 = time.perf_counter()

    def on_done(self, now: float) -> None:
        if not self.on:
            return
        self.n_done += 1
        if now - self.t0 >= self.limit:
            self.stop()

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        self.on = False
        self.spans.active = False
        self.t_end = time.perf_counter()
        self.seconds = self.t_end - self.t0
        self.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        if self._recording is not None:
            self._recording.__exit__(None, None, None)

    def inside(self, traced):
        """The program's recording cut to the requests in `traced`, each
        from its send to its completion, on the host clock the records
        share."""
        if self.recording is None or not traced:
            return None
        from repro.core.trace import Recording
        spans = [(r.t_send, r.t_done) for r in traced]

        def within(t0, t1):
            return any(a <= t0 and t1 <= b for a, b in spans)
        rec = self.recording
        return Recording(
            spans=[s for s in rec.spans if within(s.start, s.end)],
            counts=[c for c in rec.counts if within(c[0], c[0])])


def _finite(x: float) -> float:
    # JSON has no infinity: a number past every limit stands in for it
    return x if math.isfinite(x) else 1e300


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, config: Optional[dict] = None,
             control: bool = False) -> dict:
    """Warm up, measure and check one cell; returns the result object.
    `config` replaces the cell's configuration file (tests run tiny
    sizes through it); `control` switches the configuration's lower-
    precision control on."""
    import jax
    from bench.lib.clock import CompileClock

    clock = CompileClock()
    cfg_name = cell["config"]
    if config is None:
        config = traffic_mod.load_json("configs", cfg_name)
    mix = traffic_mod.load_json("traffic", cell["traffic"])
    checker = load_module("configs", cfg_name)
    kind = "per_layer" if trace else "end_to_end"
    specs = cell_metrics(bench, cell["name"], kind)
    readers = {m["name"]: load_module("metrics", m["name"]) for m in specs}

    spans = Spans()
    if trace:
        spans.install(t for r in readers.values()
                      for t in getattr(r, "SPANS", ()))
    ctl = checker.control(config) if control else None
    if ctl is not None:
        config = ctl.config
        ctl.__enter__()
    try:
        driver = drivers_mod.Driver(mix, config, seed)
        n_warm = driver.warmup()
        gc.collect()
        setup = clock.snapshot()
        setup_s = time.perf_counter() - t_start
        log(f"setup_s={setup_s} warmup_requests={n_warm} "
            f"programs_built={setup['compiles']} "
            f"build_s={setup['compile_s']} "
            f"of_them_from_cache={setup['cache_hits']}")

        trace_dir = os.path.join(TRACE_DIR, f"{cell['name']}.{seed}")
        traced = Traced(trace_dir, spans,
                        float(mix.get("trace_seconds", seconds)), trace)
        try:
            records = driver.run(seconds, on_done=traced.on_done)
        finally:
            traced.stop()
        window_s = driver.t1 - driver.t0
        inwin = clock.since(setup)
        log(f"window_s={window_s} requests={len(records)} "
            f"compiles_in_window={inwin['compiles']} "
            f"compile_s_in_window={inwin['compile_s']} "
            f"of_them_from_cache={inwin['cache_hits']}")
        late = [r.late_s for r in records] or [0.0]
        log(f"generator_late_ms max={max(late) * 1e3} "
            f"mean={sum(late) / len(late) * 1e3}")
        devs = jax.devices()[:cell["chips"]]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        driver = None
        spans.uninstall()
        gc.collect()

        checks = checker.check(records, config, seed)
    finally:
        spans.uninstall()
        if ctl is not None:
            ctl.__exit__(None, None, None)
    failed = sum(not r.ok for r in records)
    log(f"traced_window_s={traced.seconds} "
        f"traced_requests={traced.n_done}" if trace else "untraced")
    checks["requests_failed"] = {"value": failed, "limit": 0}
    correct = bool(records) and all(c["value"] <= c["limit"]
                                    for c in checks.values())

    summary = None
    dev0 = jax.devices()[0]
    if trace:
        from bench.lib.trace import find_xplane, reduce_file
        if dev0.platform != "cpu":         # the CPU has no device plane
            summary = reduce_file(find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    peaks = None
    if dev0.platform != "cpu":
        from bench.lib.peaks import peaks_for
        peaks = peaks_for(dev0.device_kind)
    inside = [r for r in records if r.t_done <= traced.t_end]
    run = RunData(config, records, window_s, setup_s, spans, summary,
                  peaks, inside, traced.inside(inside))
    metrics = {}
    for m in specs:
        v = readers[m["name"]].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": _finite(float(v)),
                                  "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = {k: {"value": _finite(float(c["value"])),
                         "limit": c["limit"]} for k, c in checks.items()}
    return out


def enable_cache() -> str:
    """JAX's persistent compilation cache at `CACHE_DIR` (through the
    program's own helper, which reads the variable set before JAX was
    imported), for every program however quick to compile, with no size
    limit: a limit set in the environment evicts the cell's own programs
    and makes every run compile again."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r} (known: {sorted(cells)})")
        return 2
    cell = cells[args.workload]
    # the compile cache lives at a fixed path inside the checkout; the
    # program's own helper reads it from this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); JAX has "
            f"{len(devs)} {devs[0].platform!r} device(s)")
        return 3
    cache_dir = enable_cache()
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)} compile_cache={cache_dir}")
    out = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=t_start)
    for name, c in out["checks"].items():
        log(f"check {name}={c['value']} limit={c['limit']}")
    print(json.dumps(out), flush=True)
    return 0
