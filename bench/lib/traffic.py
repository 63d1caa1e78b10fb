"""The one traffic generator: reads a mix file (`bench/traffic/<mix>.json`)
and draws its requests from the seed.

A mix holds one request template (`request`), sent back to back by one
closed-loop client. A sweep covers every word size, word count, write
flavor and WWL setting of its topologies. The template's fields:

  type                "sweep" | "codesign"
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

Dealing voltages in slices gives every seed the same spread of work in
another order. `representatives` lists one request per distinct shape
the template can draw, for warm-up: one per topology in `warmup_cells`
where the template cycles, and rungs at `warmup_vdd_scales` where it
gives them.
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Iterator, List

import numpy as np

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
        self.space = config["space"]
        self.rng = np.random.default_rng([int(seed), 0])
        self._cycle: List[str] = []
        self._strata: List[int] = []

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
            req["vdd_scales"] = [self._in_slice(v, v["count"], i)
                                 for i in range(v["count"])]
            req["objective"] = t["objective"]
            return req
        raise ValueError(f"unknown request type {t['type']!r}")


def representatives(mix: dict, config: dict) -> Iterator[dict]:
    """One request per distinct shape the template can draw. Drawn
    voltages do not change a shape and come from a fixed stream; a
    template's `warmup_vdd_scales` replaces its drawn rungs (the same
    count, so the same shapes, at voltages whose constants repeat)."""
    t = mix["request"]
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
