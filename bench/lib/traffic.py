"""The one traffic generator: reads a mix file (`bench/traffic/<mix>.json`)
and draws its requests from the seed.

A mix holds one request template (`request`), sent back to back by one
closed-loop client. A sweep covers every word size, word count, write
flavor and WWL setting of its topologies. The template's fields:

  type                "sweep" | "codesign" | "serve"
  fidelity            the sweep's fidelity (default: analytic)
  cells               "all" | "cycle": one topology, the next of a
                      seeded shuffle that cycles through all of them
  deck_vdd_scale      {"uniform": [lo, hi], "strata": n}: the session's
                      deck runs at that multiple of vdd; successive
                      requests take the n equal slices of the range in
                      seeded shuffles, each value uniform in its slice
  vdd_scales          {"uniform": [lo, hi], "count": n}: n sorted rungs,
                      one uniform in each of n equal slices of the range
  profiles            ["arch:shape", ...]
  objective           the co-design objective

A `serve` request is one replay: `requests` engine requests submitted
together to the configuration's model, each a prompt of `prompt_len`
tokens drawn uniformly over the vocabulary and a greedy answer of
exactly `output_len` tokens (each `{"values": [...], "weights": [...]}`),
then a co-design of the configuration's whole lattice at `vdd_scales`
against the profile the engine measured while serving them. Lengths are
dealt: each replay holds every value its weight's share of `requests`
times (largest remainders), and each prompt has a seed of its own for
its tokens. The queue is in a seeded order, sent as drawn, and `order`
(`{"median_of": m, "prompt_draws": k}`) deals every seed an order of
the same length on a first-come-first-served engine of the
configuration's slots and decode chunk (`bench.lib.schedule`): the
answers take the first seeded order whose decode chunks equal the median
of m orders from a fixed stream, and the prompts the one of k seeded
orders whose padded prefill rows lie closest to that stream's median.
(In any seeded order the last long answers to start set the replay's
length, which moved its time by 9% from seed to seed.)

Dealing voltages in slices, and lengths in shares, gives every seed the
same spread of work in another order. `representatives` lists one request per distinct shape
the template can draw, for warm-up: one per topology in `warmup_cells`
where the template cycles, and rungs at `warmup_vdd_scales` where it
gives them; for `serve`, one admission per (prompt length, batch
bucket) the engine can form, then a short replay with its co-design.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Iterator, List

import numpy as np

from bench.lib import schedule

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def lattice(space: dict, cells, word_sizes, num_words):
    """The configs of a sub-lattice, in the program's expansion order, as
    (cell, word_size, num_words, write_vt, wwlls) tuples."""
    from bench.reference.cells import CELLS
    out = []
    for c, ws, nw, vt, ls in itertools.product(
            cells, word_sizes, num_words, space["write_vts"], space["wwlls"]):
        wf = getattr(CELLS[c], "write_flavor", None)
        if vt is not None and (wf is None
                               or wf.startswith("os") != vt.startswith("os")):
            continue
        out.append((c, ws, nw, vt, ls))
    return out


class Stream:
    """A mix's request stream for one seed."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.template = mix["request"]
        self.space = config.get("space")
        self.rng = np.random.default_rng([int(seed), 0])
        self._cycle: List[str] = []
        self._strata: List[int] = []
        if self.template["type"] == "serve":
            self._median = self._median_schedule(config)

    def _cells(self, spec) -> List[str]:
        cells = self.space["cells"]
        if spec == "all":
            return list(cells)
        if not self._cycle:
            self._cycle = [cells[i] for i in
                           self.rng.permutation(len(cells))]
        return [self._cycle.pop(0)]

    def _in_slice(self, spec, n: int, k: int) -> float:
        """Uniform in the k-th of n equal slices of `spec`'s range."""
        lo, hi = spec["uniform"]
        w = (hi - lo) / n
        return float(self.rng.uniform(lo + k * w, lo + (k + 1) * w))

    def _rungs(self, v) -> List[float]:
        return [self._in_slice(v, v["count"], i) for i in range(v["count"])]

    def _deck_vdd(self, spec) -> float:
        n = int(spec["strata"])
        if not self._strata:
            self._strata = list(self.rng.permutation(n))
        return self._in_slice(spec, n, int(self._strata.pop()))

    def next(self) -> dict:
        return self.draw(self.template)

    def draw(self, t: dict) -> dict:
        """A request from template `t`: a plain dict, the sweep's fields
        (or a co-design's `sweep`), plus `deck_vdd_scale` where the
        template gives one."""
        if t["type"] == "serve":
            return self._serve(t)
        sweep = {"cells": self._cells(t["cells"]),
                 "word_sizes": list(self.space["word_sizes"]),
                 "num_words": list(self.space["num_words"]),
                 "write_vts": list(self.space["write_vts"]),
                 "wwlls": list(self.space["wwlls"])}
        if "fidelity" in t:
            sweep["fidelity"] = t["fidelity"]
        req = {"type": t["type"]}
        if "deck_vdd_scale" in t:
            req["deck_vdd_scale"] = self._deck_vdd(t["deck_vdd_scale"])
        if t["type"] == "sweep":
            req.update(sweep)
            return req
        if t["type"] == "codesign":
            v = t["vdd_scales"]
            req["sweep"] = sweep
            req["profiles"] = [dict(zip(("arch", "shape"), n.split(":")))
                               for n in t["profiles"]]
            req["vdd_scales"] = self._rungs(v)
            req["objective"] = t["objective"]
            return req
        raise ValueError(f"unknown request type {t['type']!r}")

    def _median_schedule(self, config: dict) -> tuple:
        """(decode chunks, padded prefill rows): the medians over the
        template's `median_of` orders drawn from a fixed stream."""
        t = self.template
        rng = np.random.default_rng([0, 7])
        n = int(t["requests"])
        p_len, o_len = dealt(t["prompt_len"], n), dealt(t["output_len"], n)
        eng = config["engine"]
        self._engine = (int(eng["n_slots"]), int(eng["decode_chunk"]))
        chunks, rows = [], []
        for _ in range(int(t["order"]["median_of"])):
            s = schedule.fifo(rng.permutation(o_len), *self._engine)
            chunks.append(s.chunks)
            rows.append(_rows(s.waves, rng.permutation(p_len)))
        mid = len(chunks) // 2                # an order that occurs
        return sorted(chunks)[mid], sorted(rows)[mid]

    def _order(self, p_len, o_len):
        """Prompt and answer lengths in queue order."""
        chunks, rows = self._median
        while True:
            o = self.rng.permutation(o_len)
            s = schedule.fifo(o, *self._engine)
            if s.chunks == chunks:
                break
        draws = [self.rng.permutation(p_len) for _ in
                 range(int(self.template["order"]["prompt_draws"]))]
        return min(draws, key=lambda p: abs(_rows(s.waves, p) - rows)), o

    def _serve(self, t: dict) -> dict:
        """A replay: (prompt seed, prompt length, answer length) for each
        engine request in queue order, the co-design's rungs and
        objective."""
        n = int(t["requests"])
        p, o = self._order(dealt(t["prompt_len"], n),
                           dealt(t["output_len"], n))
        seeds = self.rng.integers(0, 2 ** 31, size=n)
        return {"type": "serve",
                "prompts": [[int(seeds[i]), int(p[i]), int(o[i])]
                            for i in range(n)],
                "vdd_scales": self._rungs(t["vdd_scales"]),
                "objective": t["objective"], "codesign": True}


def _rows(waves, prompts) -> int:
    """Prompt rows the prefill dispatches of `waves` compute, padding
    included."""
    return sum(p * b for p, b in schedule.prefills(waves, prompts))


def dealt(spec: dict, n: int) -> List[int]:
    """`n` values holding each of `spec["values"]` its weight's share of
    `n` times, the remainders going to the largest fractions (ties to
    the earlier value), in the order of `values`."""
    w = np.asarray(spec["weights"], float)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    rest = np.argsort(-(share - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return [int(v) for v, c in zip(spec["values"], counts) for _ in range(c)]


def buckets(n: int) -> List[int]:
    """The batch buckets (powers of two) an admission of up to `n`
    equal-length prompts can be padded to."""
    return [1 << i for i in range(max(n - 1, 0).bit_length() + 1)]


def _serve_representatives(mix: dict, config: dict) -> Iterator[dict]:
    """Every admission shape a replay can form: for each prompt length,
    each batch bucket up to the most requests of that length that can
    be admitted together (the engine's slots, or the length's dealt
    count), as prompts answered at prefill (one token, no decode).
    Then a replay of two short prompts through decode and the
    co-design, at rungs drawn from a fixed stream."""
    t = mix["request"]
    lens = dealt(t["prompt_len"], int(t["requests"]))
    slots = int(config["engine"]["n_slots"])
    seed = 0
    for L in sorted(set(lens)):
        for b in buckets(min(slots, lens.count(L))):
            yield {"type": "serve", "codesign": False,
                   "prompts": [[seed + i, L, 1] for i in range(b)]}
            seed += b
    s = Stream(mix, config, seed=0)
    yield {"type": "serve", "codesign": True,
           "prompts": [[seed, min(lens), 2], [seed + 1, min(lens), 3]],
           "vdd_scales": s._rungs(t["vdd_scales"]),
           "objective": t["objective"]}


def representatives(mix: dict, config: dict) -> Iterator[dict]:
    """One request per distinct shape the template can draw. Drawn
    voltages do not change a shape and come from a fixed stream; a
    template's `warmup_vdd_scales` replaces its drawn rungs (the same
    count, so the same shapes, at voltages whose constants repeat)."""
    t = mix["request"]
    if t["type"] == "serve":
        yield from _serve_representatives(mix, config)
        return
    s = Stream(mix, config, seed=0)
    if t["cells"] == "all":
        cell_sets = [list(config["space"]["cells"])]
    else:
        cell_sets = [[c] for c in t["warmup_cells"]]
    for cells in cell_sets:
        req = s.draw(t)
        (req if t["type"] == "sweep" else req["sweep"])["cells"] = cells
        if "warmup_vdd_scales" in t:
            req["vdd_scales"] = list(t["warmup_vdd_scales"])
        yield req
