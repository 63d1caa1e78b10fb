"""Host spans around the program's layer entry points.

The benchmark wraps public functions and methods of the program by name
(`module`, `attr`, where `attr` may be `Class.method`). While recording
is on, each call becomes a span: a `jax.profiler.TraceAnnotation` in the
profiler's trace, for naming idle gaps, and a host-clock interval kept in
memory, for self times. A target may ask to block on the call's result
inside its span (`block`), so that device work ends inside it, and to
record the shapes of its arguments (`shapes`). The wrappers exist only
in traced runs; the end-to-end runs call the program unwrapped.
"""
from __future__ import annotations

import functools
import importlib
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class Span:
    name: str
    start: float
    end: float
    thread: int
    info: Optional[dict] = None

    @property
    def dur(self) -> float:
        return self.end - self.start


def _summary(x):
    """Shapes of array arguments, values of small scalars, recursively
    through dicts and tuples."""
    if hasattr(x, "shape"):
        return tuple(int(d) for d in x.shape)
    if isinstance(x, (bool, int, float, str)) or x is None:
        return x
    if isinstance(x, dict):
        return {k: _summary(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return {"len": len(x)}
    return type(x).__name__


@dataclass
class Spans:
    spans: List[Span] = field(default_factory=list)
    active: bool = False
    _installed: Dict[tuple, object] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    def install(self, targets) -> None:
        for t in targets:
            key = (t["module"], t["attr"])
            if key not in self._installed:
                self._wrap(**t)

    def _wrap(self, module: str, attr: str, span: str, block: bool = False,
              shapes: bool = False) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        owner = importlib.import_module(module)
        *path, name = attr.split(".")
        for p in path:
            owner = getattr(owner, p)
        orig = getattr(owner, name)
        spans = self

        @functools.wraps(orig)
        def wrapper(*args, **kwargs):
            if not spans.active:
                return orig(*args, **kwargs)
            info = None
            if shapes:
                info = {"args": [_summary(a) for a in args],
                        "kwargs": {k: _summary(v) for k, v in kwargs.items()}}
            with TraceAnnotation("bench." + span):
                t0 = time.perf_counter()
                out = orig(*args, **kwargs)
                if block:
                    out = jax.block_until_ready(out)
                t1 = time.perf_counter()
            with spans._lock:
                spans.spans.append(Span(span, t0, t1, threading.get_ident(),
                                        info))
            return out

        setattr(owner, name, wrapper)
        self._installed[(module, attr)] = (owner, name, orig)

    def uninstall(self) -> None:
        for owner, name, orig in self._installed.values():
            setattr(owner, name, orig)
        self._installed.clear()

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def union_length(intervals) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_time(spans: Spans, parent: str, children) -> float:
    """Summed duration of the `parent` spans minus the part of each that
    spans named in `children`, on the same thread, cover."""
    kids = [s for s in spans.spans if s.name in set(children)]
    total = 0.0
    for p in spans.named(parent):
        inside = [(max(k.start, p.start), min(k.end, p.end)) for k in kids
                  if k.thread == p.thread and k.end > p.start
                  and k.start < p.end]
        total += p.dur - union_length(inside)
    return total
