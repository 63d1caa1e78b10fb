"""The driver: sends a mix's requests to the system under test and
records each one's inputs, result, host-clock times and outcome.

One closed-loop client; each request runs through `Session.run` on a
fresh `Session` (on the request's scaled deck where it has one), so no
result is memoized between requests while compiled programs are shared.
A `serve` request is a replay on the run's one served model
(`bench.lib.serving.Server`, built at the first such request, which is
warm-up). A request sent before the deadline runs to completion; the
window ends at the last completion. `on_done(now)` is called after
every completion (the traced run stops its trace there). Work units per
request (transient points, cube entries, decode tokens) are counted
from the request itself.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

from bench.lib import traffic as traffic_mod


@dataclass
class Record:
    request: dict
    t_send: float
    t_done: float = 0.0
    ok: bool = False
    error: Optional[str] = None
    result: object = None
    late_s: float = 0.0              # ready-to-send -> sent
    units: dict = field(default_factory=dict)


def units(req: dict, config: dict) -> dict:
    if req["type"] == "serve":
        return {"decode_tokens": sum(o for _, _, o in req["prompts"])}
    sw = req if req["type"] == "sweep" else req["sweep"]
    p = len(traffic_mod.lattice(config["space"], sw["cells"],
                                sw["word_sizes"], sw["num_words"]))
    out = {}
    if sw.get("fidelity") == "transient":
        out["transient_points"] = p
    if req["type"] == "codesign":
        out["cube_entries"] = len(req["vdd_scales"]) * p * 2 * len(
            req["profiles"])
    return out


def _profile(name: str, config: dict):
    from repro.workloads.profiler import Profile
    return Profile(**config["profiles"][name])


def to_query(req: dict, config: dict):
    from repro.api import CoDesignQuery, SweepQuery
    sw = req if req["type"] == "sweep" else req["sweep"]
    kw = {k: tuple(sw[k]) for k in ("cells", "word_sizes", "num_words",
                                     "write_vts", "wwlls")}
    if sw.get("fidelity", "analytic") != "analytic":
        kw.update(fidelity=sw["fidelity"], sim_steps=config["sim_steps"],
                  precision=config["precision"])
    sweep = SweepQuery(**kw)
    if req["type"] == "sweep":
        return sweep
    if req["type"] == "codesign":
        return CoDesignQuery(
            profiles=tuple(_profile(f"{p['arch']}:{p['shape']}", config)
                           for p in req["profiles"]),
            sweep=sweep, vdd_scales=tuple(req["vdd_scales"]),
            objective=req["objective"],
            allow_refresh=config["allow_refresh"],
            max_banks=config["max_banks"])
    raise ValueError(f"cannot send {req['type']!r}")


def _deck(req: dict):
    from repro.core.techfile import SYN40, with_vdd_scale
    return with_vdd_scale(SYN40, req.get("deck_vdd_scale", 1.0))


class Driver:
    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix, self.config, self.seed = mix, config, seed
        self.server = None

    def reseed(self, seed: int) -> None:
        """Draw the next window's requests (and a served model's
        weights) from `seed`, keeping every compiled program."""
        self.seed = seed
        if self.server is not None:
            self.server.reseed(seed)

    def send(self, req: dict):
        if req["type"] == "serve":
            if self.server is None:
                from bench.lib.serving import Server
                self.server = Server(self.config, self.seed)
            return self.server.replay(req)
        from repro.api import Session
        return Session(tech=_deck(req)).run(to_query(req, self.config))

    def warmup(self) -> int:
        n = 0
        for req in traffic_mod.representatives(self.mix, self.config):
            self.send(req)
            n += 1
        return n

    def run(self, seconds: float, on_done=None) -> List[Record]:
        stream = traffic_mod.Stream(self.mix, self.config, self.seed)
        records: List[Record] = []
        t0 = time.perf_counter()
        self.t0, deadline = t0, t0 + seconds
        ready = t0
        while True:
            req = stream.next()
            now = time.perf_counter()
            if now >= deadline:
                break
            rec = Record(req, now, late_s=now - ready,
                         units=units(req, self.config))
            try:
                rec.result = self.send(req)
                rec.ok = True
            except Exception as e:                       # noqa: BLE001
                rec.error = f"{type(e).__name__}: {e}"
            rec.t_done = time.perf_counter()
            records.append(rec)
            if on_done is not None:
                on_done(rec.t_done)
            ready = time.perf_counter()
        self.t1 = records[-1].t_done if records else time.perf_counter()
        return records
