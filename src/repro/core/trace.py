"""Spans and counters inside the program.

`span(name)` marks one stretch of host work at a layer boundary and
`count(name, n)` adds to a counter. Both do nothing unless a
`recording()` is open: `span` then returns one shared no-op context (no
allocation, no annotation, no clock read) whose `with` target is None.
While a recording is open, each span enters
`jax.profiler.TraceAnnotation("gcram." + name)`, so a profiler trace
shows it on the host plane on the device trace's clock, and is kept in
memory with its parent (the enclosing span on the same thread), its
request id, its thread and its `attrs`, a dict the caller fills only
when the `with` target is a span. `request(name)` is a span that starts
a new request id, which every span below it inherits.

    with trace.recording() as rec:
        session.run(query)
    rec.self_time("char_batch.prep", ())

Spans sit at boundaries only: never inside a jitted function, a
per-point loop or a Newton step.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import Counter
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Iterator, List, Optional, Tuple

import jax


@dataclass(frozen=True)
class SpanRecord:
    name: str
    start: float                  # time.perf_counter(), seconds
    end: float
    id: int
    parent: Optional[int]         # id of the enclosing span, same thread
    request: Optional[int]
    thread: int
    attrs: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


def _union_length(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    return total + (cur_e - cur_s if cur_e is not None else 0.0)


@dataclass
class Recording:
    """What the program recorded while a `recording()` was open: its
    spans, and each `count()` as (time, counter, n)."""
    spans: List[SpanRecord] = field(default_factory=list)
    counts: List[Tuple[float, str, int]] = field(default_factory=list)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    @property
    def counters(self) -> Counter:
        out: Counter = Counter()
        for _, name, n in self.counts:
            out[name] += n
        return out

    def named(self, name: str) -> List[SpanRecord]:
        return [s for s in self.spans if s.name == name]

    def self_time(self, name: str, children) -> float:
        """Summed duration of the `name` spans minus the part of each
        that spans named in `children`, on the same thread, cover."""
        children = set(children)
        kids = [s for s in self.spans if s.name in children]
        total = 0.0
        for p in self.named(name):
            total += p.dur - _union_length(
                (max(k.start, p.start), min(k.end, p.end)) for k in kids
                if k.thread == p.thread and k.end > p.start
                and k.start < p.end)
        return total


_ACTIVE: Optional[Recording] = None
_LOCAL = threading.local()
_SPAN_IDS = itertools.count(1)
_REQUEST_IDS = itertools.count(1)


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("rec", "name", "attrs", "new_request", "annotation",
                 "start", "id", "parent", "request")

    def __init__(self, rec: Recording, name: str, new_request: bool):
        self.rec, self.name, self.attrs = rec, name, {}
        self.new_request = new_request

    def __enter__(self):
        stack = _LOCAL.__dict__.setdefault("stack", [])
        top = stack[-1] if stack else None
        self.id = next(_SPAN_IDS)
        self.parent = top.id if top is not None else None
        self.request = (next(_REQUEST_IDS) if self.new_request else
                        top.request if top is not None else None)
        self.annotation = jax.profiler.TraceAnnotation("gcram." + self.name)
        self.annotation.__enter__()
        stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _LOCAL.stack.pop()
        self.annotation.__exit__(*exc)
        rec = SpanRecord(self.name, self.start, end, self.id, self.parent,
                         self.request, threading.get_ident(), self.attrs)
        with self.rec._lock:
            self.rec.spans.append(rec)
        return False


def span(name: str):
    """Context manager over one stretch of host work (see module doc)."""
    rec = _ACTIVE
    return _OFF if rec is None else _Span(rec, name, False)


def request(name: str):
    """A span that starts a new request id for every span below it."""
    rec = _ACTIVE
    return _OFF if rec is None else _Span(rec, name, True)


def count(name: str, n: int = 1) -> None:
    """Add `n` to the counter `name` while a recording is open."""
    rec = _ACTIVE
    if rec is not None:
        with rec._lock:
            rec.counts.append((time.perf_counter(), name, n))


@contextmanager
def recording() -> Iterator[Recording]:
    """Record every span and counter of the process until exit."""
    global _ACTIVE
    if _ACTIVE is not None:
        raise RuntimeError("a trace recording is already open")
    rec = _ACTIVE = Recording()
    try:
        yield rec
    finally:
        _ACTIVE = None
