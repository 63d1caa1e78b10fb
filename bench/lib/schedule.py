"""A queue's schedule on a first-come-first-served continuous-batching
engine, replayed on the host from the requests' lengths alone.

The engine this describes holds `n_slots` sequences. Whenever slots are
free, it admits waiting requests in queue order into them (a wave), one
prefill dispatch per prompt length in the wave, the batch padded to a
power of two; a request's first token comes from its prefill. Decode
runs in chunks of `chunk` steps over every slot, a slot freezing once it
has emitted its budget. The next chunk is dispatched before the last
one's tokens are read back, so a slot freed by chunk n takes a new
request in chunk n + 2.

Which requests share a wave depends on the answer lengths alone, so
`fifo(answers, n_slots, chunk)` gives the decode chunks the queue takes
and its waves (queue positions), and `prefills(waves, prompts)` the
prefill dispatches, (prompt length, padded batch), of any prompt lengths
put in that order. The traffic generator uses them to deal every seed an
order of the same length (`bench.lib.traffic`); nothing here reads the
program.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import List, Sequence, Tuple


@dataclass
class Schedule:
    chunks: int = 0
    waves: List[List[int]] = field(default_factory=list)


def fifo(answers: Sequence[int], n_slots: int, chunk: int) -> Schedule:
    """`answers`: each request's answer length, in queue order."""
    queue = deque(enumerate(int(o) for o in answers))
    held = [None] * n_slots         # request a slot holds (host's view)
    dev = [0] * n_slots             # tokens still to emit, device side
    host = [0] * n_slots            # ... as read back so far
    out = Schedule()

    def admit():
        free = [s for s in range(n_slots) if held[s] is None]
        wave = []
        for s in free[:len(queue)]:
            rid, o = queue.popleft()
            wave.append(rid)
            if o > 1:                       # else done at its prefill
                held[s], dev[s], host[s] = rid, o - 1, o - 1
        if wave:
            out.waves.append(wave)

    def may_emit():
        return any(held[s] is not None and dev[s] > 0
                   for s in range(n_slots))

    def dispatch():
        emitted = {}
        for s in range(n_slots):
            if held[s] is not None:
                emitted[s] = (held[s], min(chunk, dev[s]))
                dev[s] -= emitted[s][1]
        out.chunks += 1
        return emitted

    def read_back(emitted):
        for s, (rid, n) in emitted.items():
            if held[s] != rid:
                continue                    # slot re-admitted since
            host[s] -= n
            if host[s] == 0:
                held[s] = None

    pending = None
    while True:
        if pending is None:
            admit()
            while not may_emit() and queue:
                admit()
            if not may_emit():
                break
        nxt = dispatch() if may_emit() else None
        if pending is not None:
            read_back(pending)
        admit()
        pending = nxt
    return out


def prefills(waves: Sequence[Sequence[int]],
             prompts: Sequence[int]) -> List[Tuple[int, int]]:
    """(prompt length, padded batch) of each prefill dispatch, in order."""
    out = []
    for wave in waves:
        groups = {}
        for rid in wave:
            p = int(prompts[rid])
            groups[p] = groups.get(p, 0) + 1
        out += [(p, 1 << (b - 1).bit_length()) for p, b in groups.items()]
    return out
