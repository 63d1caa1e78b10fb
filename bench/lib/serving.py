"""The served model of a `serve` mix: one `ServeEngine` for the run.

Set-up builds the configuration's model (`ModelConfig(**config["model"])`),
its weights from the seed (`bench.lib.weights`) and one device-mode
engine with a telemetry collector attached: a server runs continuously,
and every replay goes through the same compiled programs.

A replay submits its requests together (greedy, no stop token, prompts
uniform over the vocabulary from each prompt's own seed), serves them
until the engine drains, takes the collector's window of that replay,
and, where the request asks, runs the program's own path from the live
engine into co-design: `Session.codesign_measured` on a fresh session
over the whole lattice of the co-design configuration the serving one
names (`"codesign"`) at the drawn rungs. A replay fails unless every
request emitted exactly its budget. Its result keeps the served tokens,
the window's counters (`WINDOW`, for the check) and the report.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.lib import traffic, weights

# the telemetry window's counters a replay keeps
WINDOW = ("t_start_s", "t_end_s", "decode_steps", "decode_tokens",
          "prefill_tokens", "n_submitted", "n_admitted", "n_retired",
          "kv_row_steps", "kv_lifetimes_s")


def codesign_config(config: dict) -> dict:
    """The co-design configuration a serving one names: its lattice,
    limits and objective."""
    return traffic.load_json("configs", config["codesign"])


def prompt_tokens(seed: int, length: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([int(seed), 1]).integers(
        0, vocab, size=int(length), dtype=np.int32)


class Server:
    def __init__(self, config: dict, seed: int):
        from repro.runtime.telemetry import TelemetryCollector
        from repro.serving.engine import ServeEngine
        self.config = config
        self.codesign = codesign_config(config)
        self.cfg = weights.model_config(config)
        self.telemetry = TelemetryCollector()
        eng = config["engine"]
        self.engine = ServeEngine(
            self.cfg, weights.make(config, seed), n_slots=eng["n_slots"],
            window=eng["window"], mode="device",
            decode_chunk=eng["decode_chunk"], telemetry=self.telemetry)
        self._rid = 0

    def reseed(self, seed: int) -> None:
        """New weights from `seed` for the same engine and programs."""
        self.engine.params = weights.make(self.config, seed)

    def _sweep(self):
        from repro.api import SweepQuery
        space = self.codesign["space"]
        return SweepQuery(**{k: tuple(space[k]) for k in (
            "cells", "word_sizes", "num_words", "write_vts", "wwlls")})

    def replay(self, req: dict) -> dict:
        from repro.serving.engine import Request
        V = self.cfg.vocab_size
        reqs: List[Request] = []
        for seed, p_len, o_len in req["prompts"]:
            reqs.append(Request(self._rid, prompt_tokens(seed, p_len, V),
                                max_new_tokens=int(o_len), temperature=0.0,
                                eos_id=None))
            self._rid += 1
        self.telemetry.snapshot(reset=True)
        for r in reqs:
            self.engine.submit(r)
        self.engine.run(max_steps=1 << 30)
        win = self.telemetry.snapshot(reset=True)
        short = [(r.rid, len(r.out_tokens or ()), o)
                 for r, (_, _, o) in zip(reqs, req["prompts"])
                 if len(r.out_tokens or ()) != o]
        if short:
            raise RuntimeError(f"requests emitted other than their budget "
                               f"(rid, emitted, budget): {short[:4]}")
        out = {"tokens": [list(r.out_tokens) for r in reqs],
               "decode_steps": win.decode_steps,
               "window": {k: getattr(win, k) for k in WINDOW}}
        if req.get("codesign"):
            from repro.api import Session
            from repro.core.techfile import SYN40
            c = self.codesign
            report = Session(tech=SYN40).codesign_measured(
                [win], self.cfg, sweep=self._sweep(),
                vdd_scales=tuple(req["vdd_scales"]),
                objective=req["objective"],
                allow_refresh=c["allow_refresh"], max_banks=c["max_banks"])
            out["report"] = report
            out["profile"] = dataclasses.asdict(report.query.profiles[0])
        return out
