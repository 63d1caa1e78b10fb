"""The program's own spans and counters (`repro.core.trace`), for the
per-layer readers of a traced run.

Temporary: the harness hands a reader the run and nothing of the
program's recording, so the readers open one themselves. A `benchmark`
PR that opens the recording in the harness's `Traced` and hands it to
readers as a `RunData` field deletes this file.

A reader that needs the recording calls `record()` when the harness
loads it, which happens only for a traced run and before its warm-up;
the first call opens one recording for the process. `window(run)`
closes it and returns a `Recording` of what it holds inside the requests
completed while traced (each from its send to its completion, on the
host clock the records share). A run that raises before its readers
read leaves its recording open: the next run's `record()` closes it
(it holds records, and no program call comes between loading one run's
readers), and so does `close()`, which runs at the process's exit. On a
program without `repro.core.trace`, `record()` does nothing and
`window()` gives None, so the readers read None.
"""
from __future__ import annotations

import atexit

_open = None        # the open recording's context manager
_last = None        # the recording it gave


def record() -> None:
    global _open, _last
    if _open is not None and not (_last.spans or _last.counts):
        return                          # opened by a reader of this run
    close()
    _last = None
    try:
        from repro.core import trace
    except ImportError:                 # a program without its own spans
        return
    _open = trace.recording()
    _last = _open.__enter__()


def close() -> None:
    global _open
    if _open is not None:
        _open.__exit__(None, None, None)
        _open = None


atexit.register(close)


def window(run):
    close()
    if _last is None or not run.traced:
        return None
    from repro.core.trace import Recording
    inside = [(r.t_send, r.t_done) for r in run.traced]

    def within(t0, t1):
        return any(a <= t0 and t1 <= b for a, b in inside)
    return Recording(
        spans=[s for s in _last.spans if within(s.start, s.end)],
        counts=[c for c in _last.counts if within(c[0], c[0])])
