"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

Read with `jax.profiler.ProfileData`, which needs nothing but JAX:

  * device planes are those named `/device:TPU:<i>` (or GPU); a
    device's busy time is the union of the intervals of its program
    executions (the `XLA Modules` line; a TPU runs a program's
    operations back to back, and this line has a few events per program
    where `XLA Ops` has one per operation, millions a second), else of
    every event of the plane, inside the window;
  * the window is the harness's `bench.window` annotation on the host
    plane, on the same clock;
  * program time: durations on the `XLA Modules` line, by module name
    with its `(<id>)` suffix removed, so `jit_run(12)` counts as
    `jit_run`; the breakdown's device operations are these programs;
  * idle gaps: the window minus the union of every device's busy
    intervals, each gap attributed to the innermost `bench.*` host span
    that covers its midpoint (`host:idle` where none does).

Times are nanoseconds in the trace and seconds out of it.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_SUFFIX = re.compile(r"\(-?\d+\)$")
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


def merge(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclass
class TraceSummary:
    window_s: float
    n_devices: int
    busy_s: float                         # mean over devices
    module_s: Dict[str, float] = field(default_factory=dict)
    module_calls: Dict[str, int] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.module_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}

    def module_time(self, prefix: str) -> float:
        return sum(v for k, v in self.module_s.items()
                   if k == prefix or k.startswith(prefix + "."))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns), e


def reduce_planes(planes, window: Optional[Tuple[float, float]] = None
                  ) -> TraceSummary:
    """`planes`: iterable of objects with `.name` and `.lines`, each line
    with `.name` and `.events` (`.name`, `.start_ns`, `.duration_ns`,
    `.stats`), as `ProfileData` gives them."""
    host_spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in planes:
        lines = list(plane.lines)
        if _DEVICE.match(plane.name):
            devices.append((plane.name, lines))
            continue
        for line in lines:
            for name, s, e, _ in _events(line):
                if name.startswith("bench."):
                    host_spans.append((s, e, name[len("bench."):]))
    if window is None:
        wins = [(s, e) for s, e, n in host_spans if n == "window"]
        if not wins:
            raise ValueError("trace has no bench.window annotation")
        window = max(wins, key=lambda w: w[1] - w[0])
    lo, hi = window
    if not devices:
        raise ValueError("trace has no device plane")

    busy_all, busy_each = [], []
    module_s: Dict[str, float] = defaultdict(float)
    module_calls: Dict[str, int] = defaultdict(int)
    for _, lines in devices:
        by_name = {ln.name: ln for ln in lines}
        busy_lines = [by_name["XLA Modules"]] if "XLA Modules" in by_name \
            else lines
        ivs = []
        for line in busy_lines:
            for name, s, e, _ in _events(line):
                if e <= lo or s >= hi:
                    continue
                ivs.append((s, e))
                m = module_name(name)
                module_s[m] += (min(e, hi) - max(s, lo)) * 1e-9
                module_calls[m] += 1
        ivs = merge(clip(ivs, lo, hi))
        busy_each.append(sum(e - s for s, e in ivs))
        busy_all.extend(ivs)

    busy = merge(busy_all)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    inner = sorted((h for h in host_spans if h[2] != "window"),
                   key=lambda h: h[1] - h[0])
    idle_by_span: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        span = next((n for hs, he, n in inner if hs <= mid <= he),
                    "host:idle")
        idle_by_span[span] += (e - s) * 1e-9
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, n_devices=len(devices),
        busy_s=sum(busy_each) / len(busy_each) * 1e-9,
        module_s=dict(module_s), module_calls=dict(module_calls),
        idle_by_span=dict(idle_by_span))


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
