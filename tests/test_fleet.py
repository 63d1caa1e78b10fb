"""Fault-tolerance layer: lease claim/expiry/steal races, store
self-heal after torn writes and checksum corruption, fleet retries /
poison quarantine / degraded mode, and the subprocess fleet surviving a
worker SIGKILLed mid-wave with zero duplicate evaluations."""
import json
import os
import threading
import time

from repro.api import ArtifactStore, Session, SweepQuery
from repro.api.leases import LeaseManager
from repro.launch.compile_service import CompileService
from repro.launch.fleet import Fleet
from repro.testing.faults import FaultInjector, FaultSpec

TINY = SweepQuery(cells=("gc2t_nn",), word_sizes=(8,), num_words=(16,),
                  write_vts=(None,), wwlls=(False,))


def _tiny_spec(num_words=16, ident="r0", tenant="t0"):
    return {"id": ident, "tenant": tenant, "query": {
        "type": "sweep", "cells": ["gc2t_nn"], "word_sizes": [8],
        "num_words": [num_words], "write_vts": [None], "wwlls": [False]}}


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------

def test_lease_single_winner_under_threads(tmp_path):
    mgr = LeaseManager(tmp_path, ttl_s=30.0, heartbeat=False)
    wins, barrier = [], threading.Barrier(16)

    def race():
        barrier.wait()
        lease = mgr.try_claim("points-abc")
        if lease is not None:
            wins.append(lease)

    threads = [threading.Thread(target=race) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(wins) == 1
    wins[0].release()
    assert mgr.try_claim("points-abc") is not None  # released -> claimable


def test_lease_expiry_allows_steal(tmp_path):
    dead = LeaseManager(tmp_path, owner="dead", ttl_s=0.15,
                        heartbeat=False)
    assert dead.try_claim("points-k") is not None
    thief = LeaseManager(tmp_path, owner="thief", ttl_s=0.15,
                         heartbeat=False)
    assert thief.try_claim("points-k") is None      # still live
    time.sleep(0.3)
    lease = thief.try_claim("points-k")             # expired: steal
    assert lease is not None and lease.stolen
    assert thief.counts["steals"] == 1


def test_steal_race_has_single_winner(tmp_path):
    dead = LeaseManager(tmp_path, owner="dead", ttl_s=0.1,
                        heartbeat=False)
    assert dead.try_claim("points-k") is not None
    time.sleep(0.25)
    wins, barrier = [], threading.Barrier(8)

    def race(i):
        mgr = LeaseManager(tmp_path, owner=f"thief{i}", ttl_s=0.1,
                           heartbeat=False)
        barrier.wait()
        lease = mgr.try_claim("points-k")
        if lease is not None:
            wins.append(lease)

    threads = [threading.Thread(target=race, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(wins) == 1 and wins[0].stolen


def test_heartbeat_keeps_lease_alive(tmp_path):
    owner = LeaseManager(tmp_path, owner="live", ttl_s=0.3, heartbeat=True)
    assert owner.try_claim("points-k") is not None
    thief = LeaseManager(tmp_path, owner="thief", ttl_s=0.3,
                         heartbeat=False)
    time.sleep(0.6)        # two TTLs: heartbeats must have re-touched
    assert thief.try_claim("points-k") is None
    owner.close()


def test_acquire_waits_for_publish(tmp_path):
    owner = LeaseManager(tmp_path, owner="o", ttl_s=30.0, heartbeat=False)
    lease = owner.try_claim("points-k")
    box, got = {}, []

    def waiter():
        got.append(LeaseManager(tmp_path, owner="w", ttl_s=30.0,
                                heartbeat=False)
                   .acquire("points-k", lambda: box.get("v"), timeout=5))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    box["v"] = 42          # publish, THEN release — the executor's order
    lease.release()
    t.join()
    assert got == [("have", 42)]


def test_acquire_steals_from_dead_owner(tmp_path):
    dead = LeaseManager(tmp_path, owner="dead", ttl_s=0.15,
                        heartbeat=False)
    assert dead.try_claim("points-k") is not None   # never publishes
    mgr = LeaseManager(tmp_path, owner="w", ttl_s=0.15, heartbeat=False)
    kind, lease = mgr.acquire("points-k", lambda: None, timeout=5)
    assert kind == "own" and lease.stolen


def test_eval_log_and_duplicates(tmp_path):
    a = LeaseManager(tmp_path, owner="a", ttl_s=1.0, heartbeat=False)
    b = LeaseManager(tmp_path, owner="b", ttl_s=1.0, heartbeat=False)
    a.log_eval("points-x", "fresh")
    b.log_eval("points-y", "fresh")
    b.log_eval("points-y", "heal")      # sanctioned recovery, not a dup
    assert LeaseManager.duplicate_evals(tmp_path) == {}
    b.log_eval("points-x", "fresh")     # the forbidden case
    assert LeaseManager.duplicate_evals(tmp_path) == {"points-x": 2}


# ---------------------------------------------------------------------------
# store durability
# ---------------------------------------------------------------------------

def test_store_sweeps_stale_tmp_and_prunes(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put("points-abc", {"v": 1})
    stale = tmp_path / "points" / "dead.tmp"
    stale.write_text("torn")
    old = time.time() - 3600
    os.utime(stale, (old, old))
    assert store.sweep_tmp(600.0) == 1 and not stale.exists()
    assert store.get("points-abc") == {"v": 1}      # artifacts untouched
    os.utime(store._path("points-abc"), (old, old))
    assert store.prune(600.0) == 1
    assert store.get("points-abc") is None          # pruned -> recompute
    assert store.stats()["swept"] == 1 and store.stats()["pruned"] == 1


def test_store_detects_torn_write(tmp_path):
    store = ArtifactStore(str(tmp_path))
    with FaultInjector(FaultSpec(tear_rate=1.0)).install(store=store) as inj:
        store.put("points-abc", {"rows": [1.5, 2.5]})
        assert inj.counts["torn_writes"] == 1
    assert store.get("points-abc") is None          # miss, not garbage
    assert store.corrupt == 1
    store.put("points-abc", {"rows": [1.5, 2.5]})   # recompute repairs
    assert store.get("points-abc") == {"rows": [1.5, 2.5]}


def test_store_detects_checksum_corruption(tmp_path):
    store = ArtifactStore(str(tmp_path))
    store.put("points-abc", {"rows": [1.5, 2.5]})
    with FaultInjector(FaultSpec(corrupt_rate=1.0)).install(store=store) \
            as inj:
        assert store.get("points-abc") is None
        assert inj.counts["corrupted_reads"] == 1
    assert store.corrupt == 1
    assert not store.has("points-abc")              # unlinked for repair


def test_leased_sessions_share_one_evaluation(tmp_path):
    d = str(tmp_path)
    t1 = Session(store=d, leases=True).run(TINY)
    t2 = Session(store=d, leases=True).run(TINY)    # pure store hit
    log = LeaseManager.read_eval_log(d)
    assert sum(c.get("fresh", 0) for c in log.values()) == len(log)
    assert LeaseManager.duplicate_evals(d) == {}
    for a, b in zip(t1.points, t2.points):
        assert a.t_read_s == b.t_read_s and a.area_um2 == b.area_um2


def test_executor_heals_torn_artifact(tmp_path):
    d = str(tmp_path)
    store = ArtifactStore(d)
    with FaultInjector(FaultSpec(tear_rate=1.0)).install(store=store):
        t1 = Session(store=store,
                     leases=LeaseManager(d, heartbeat=False)).run(TINY)
    s2 = Session(store=d, leases=True)
    t2 = s2.run(TINY)                   # torn artifact -> heal recompute
    assert s2.store.corrupt == 1
    log = LeaseManager.read_eval_log(d)
    assert sum(c.get("heal", 0) for c in log.values()) == 1
    assert LeaseManager.duplicate_evals(d) == {}
    for a, b in zip(t1.points, t2.points):
        assert a.t_read_s == b.t_read_s and a.area_um2 == b.area_um2


# ---------------------------------------------------------------------------
# compile-service satellites
# ---------------------------------------------------------------------------

def test_drain_isolates_serialization_failure(monkeypatch):
    from repro.api.queries import Query
    from repro.launch import compile_service as cs

    class _BadResult:
        def as_dict(self):
            raise RuntimeError("unserializable result")

    class _BadQuery(Query):
        def run(self, session):
            return _BadResult()

    real_parse = cs.parse_query
    monkeypatch.setattr(
        cs, "parse_query",
        lambda spec, tech: _BadQuery() if spec.get("type") == "boom"
        else real_parse(spec, tech))
    svc = CompileService(wave_size=8)
    svc.submit({"id": "bad", "query": {"type": "boom"}})
    svc.submit(_tiny_spec(ident="good"))
    out = {r["id"]: r for r in svc.drain()}
    assert out["good"]["ok"]                         # wave completed
    assert not out["bad"]["ok"]
    assert "serialization" in out["bad"]["error"]
    assert out["bad"]["retryable"] is False          # deterministic


def test_serve_stream_drains_partial_waves():
    svc = CompileService(wave_size=64)

    def slow_producer():
        yield json.dumps(_tiny_spec(ident="first"))
        time.sleep(0.4)                 # far longer than the idle window
        yield json.dumps(_tiny_spec(ident="second"))

    t0 = time.time()
    lines = list(svc.serve_stream(slow_producer(), max_wait_s=0.05))
    assert [json.loads(l)["id"] for l in lines] == ["first", "second"]
    assert all(json.loads(l)["ok"] for l in lines)
    assert svc.waves == 2               # partial waves, not one big one
    assert time.time() - t0 < 30


# ---------------------------------------------------------------------------
# fleet
# ---------------------------------------------------------------------------

def test_fleet_degrades_to_inline_when_spawn_fails(tmp_path):
    with Fleet(str(tmp_path / "spool"), str(tmp_path / "store"),
               n_workers=2, python="/nonexistent/python",
               max_attempts=2) as fleet:
        assert fleet.degraded
        resp = fleet.run([_tiny_spec(ident="a"), _tiny_spec(ident="b")])
    assert all(r["ok"] for r in resp)
    assert [r["id"] for r in resp] == ["a", "b"]
    assert fleet.counters["spawn_failures"] == 2
    assert LeaseManager.duplicate_evals(str(tmp_path / "store")) == {}


def test_fleet_quarantines_poison_inline(tmp_path):
    with Fleet(str(tmp_path / "spool"), str(tmp_path / "store"),
               n_workers=1, python="/nonexistent/python",
               max_attempts=3, backoff_s=0.01,
               fault_specs={"inline": "poison=POISON"}) as fleet:
        resp = fleet.run([_tiny_spec(ident="POISON-1"),
                          _tiny_spec(ident="fine")])
    poison, fine = resp
    assert not poison["ok"] and poison["quarantined"]
    assert poison["attempts"] == 3
    assert fine["ok"] and "quarantined" not in fine


def test_fleet_rejects_invalid_query_without_retry(tmp_path):
    with Fleet(str(tmp_path / "spool"), str(tmp_path / "store"),
               n_workers=1, python="/nonexistent/python",
               max_attempts=5) as fleet:
        resp = fleet.run([{"id": "bad", "query": {"type": "nonsense"}}])
    assert not resp[0]["ok"] and resp[0]["attempts"] == 1
    assert "quarantined" not in resp[0]  # deterministic error, no retry


def test_fleet_workers_stay_off_the_chip_unless_given_it(tmp_path,
                                                         monkeypatch):
    """One chip, one process: a worker runs with JAX_PLATFORMS=cpu unless
    the caller names it in `chip_workers`."""
    from repro.launch import fleet as fleet_mod
    envs = {}

    class Proc:
        def __init__(self, cmd, env, **_):
            envs[cmd[cmd.index("--worker-id") + 1]] = env

        def poll(self):
            return 0

        def wait(self, timeout=None):
            return 0

    monkeypatch.setattr(fleet_mod.subprocess, "Popen", Proc)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with Fleet(str(tmp_path / "spool"), None, n_workers=2,
               chip_workers=("w1",)):
        pass
    assert envs["w0"]["JAX_PLATFORMS"] == "cpu"
    assert envs["w1"]["JAX_PLATFORMS"] == "tpu"


def test_fleet_survives_worker_killed_mid_wave(tmp_path):
    spool, store = str(tmp_path / "spool"), str(tmp_path / "store")
    reqs = [_tiny_spec(nw, f"r{i}", f"t{i % 2}")
            for i, nw in enumerate((16, 32, 16, 64, 32))]
    svc = CompileService(wave_size=8)
    lines = svc.serve_lines(json.dumps(r) for r in reqs)
    base = {r["id"]: r for r in map(json.loads, lines)}
    with Fleet(spool, store, n_workers=2, lease_ttl_s=2.0,
               backoff_s=0.2, max_attempts=5, deadline_s=120.0,
               fault_specs={"w0": "die_after_puts=1"}) as fleet:
        resp = fleet.run(reqs, timeout_s=300)
        stats = fleet.stats()
    assert all(r["ok"] for r in resp)
    assert stats["worker_deaths"] == 1
    assert stats["workers"]["w1"]["platform"] == "cpu"
    assert stats["retries"] >= 1
    assert LeaseManager.duplicate_evals(store) == {}
    for r in resp:          # bit-identical to the in-process baseline
        b = base[r["id"]]
        assert json.dumps(r["result"], sort_keys=True) == \
            json.dumps(b["result"], sort_keys=True)
