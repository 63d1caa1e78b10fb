"""Batched serving engine: prefill + decode with slot-based continuous
batching (vLLM-style at the granularity JAX's static shapes allow).

The engine owns a fixed decode batch of `n_slots` sequences and a KV
cache sized (slots, window): for K/V heads one stacked array per K and V
of (layers, slots, window, kv_heads * head_dim), the layout every program
here reads and writes (prefill's rows are inserted as they come). Requests
are queued (deque, O(1) FIFO);
whenever slots free (EOS or max tokens) waiting requests are admitted in
prompt-length groups: equal-length prompts prefill in ONE batched
dispatch, with the batch dim padded to a power-of-two bucket so the
compiled program is reused across admission waves of different sizes
(mirroring `char_batch`'s lattice bucketing; the prompt length itself is
never padded — right-padding would corrupt recurrent-state caches and
ring seeding, so buckets are keyed (prompt_len, batch_bucket)).

Decode is fully device-resident: `Model.decode_loop` fuses
`decode_chunk` steps of decode_step + sampling (greedy and top-k
temperature via `jax.lax.top_k` + `jax.random.categorical`) into one
jitted lax.scan whose carry (cache, feedback token, pos, emitted
counter, done mask, PRNG key) is donated, so the KV cache updates in
place (each layer writes its row into the carried cache; the chunk makes
no cache-sized copy) and the host syncs ONCE per `decode_chunk` tokens
instead of once per token. Finished slots (tokens-emitted >= max_new_tokens, or EOS
hit) freeze inside the chunk via the carried done mask, so a slot that
stops mid-chunk emits exactly its budget.

All host->device slot updates (admission) are surgical `.at[idx].set`
scatters rather than whole-array uploads, so they compose correctly
with an in-flight chunk under JAX async dispatch. `run()` exploits
that: it dispatches chunk N+1 BEFORE reconciling chunk N's tokens, so
host-side bookkeeping (retire, admit, prefill dispatch) overlaps device
compute; a freed slot rejoins one chunk later, which is the
K-vs-latency tradeoff documented in the README. `step()` stays fully
synchronous (admit -> one chunk -> reconcile) for lifecycle tests.

mode="host" keeps the original per-token loop (device->host logits sync
+ np-rng host sampling every token) as the parity and throughput
reference: greedy token streams are exactly equal across modes;
stochastic streams draw from the same top-k support but different rngs
(see serving/sampling.py). `host_syncs` counts blocking device->host
transfers in both modes for the bench_serve scoreboard.

Observability: the engine keeps a completed-request log (`request_log`,
RequestStats entries stamped by the engine clock) and accepts an
optional duck-typed `telemetry` collector (repro.runtime). Telemetry
hooks consume ONLY host-side values the engine already reconciled —
the np token/live arrays pulled once per chunk, host-tracked per-slot
context lengths, python queue depths — so an attached collector adds
ZERO device syncs and cannot perturb token streams (asserted in
tests/test_runtime.py).

Spans and counters (`repro.core.trace`, recorded only while a recording
is open, at host boundaries): `serve.admit` around each prefill dispatch
(attrs `rows`, the prompt rows, and `padded_rows`, with the batch
bucket's padding), `serve.chunk` around each decode dispatch; at each
chunk's existing sync, `serve.slot_steps` (every slot's steps the chunk
computed), `serve.slot_steps_live` (the steps that emitted a token) and,
for a MoE model, `moe.held_assignments` (live tokens' expert assignments
that land on the experts held here), all from the arrays that sync
already brings to the host.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.models.model import Model
from repro.serving.sampling import sample_host, sample_tokens


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 -> greedy
    top_k: int = 40
    eos_id: Optional[int] = None  # emitting this token stops the request
    out_tokens: Optional[list] = None
    # engine-stamped lifecycle times (engine clock, seconds)
    t_submit_s: Optional[float] = None
    t_admit_s: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class RequestStats:
    """Lifecycle record of one COMPLETED request, appended to
    `ServeEngine.request_log` at retire (the engine previously forgot
    everything but the token stream). Timestamps come from the engine
    clock — `time.monotonic` by default, or an attached telemetry
    collector's virtual clock, so deterministic replays yield
    deterministic stats. In this engine the first token is sampled
    INSIDE the prefill dispatch, so `t_first_s == t_admit_s`; both are
    kept because the schema outlives that implementation detail."""
    rid: int
    prompt_len: int
    emitted: int
    t_submit_s: float
    t_admit_s: float
    t_first_s: float
    t_retire_s: float

    @property
    def queue_wait_s(self) -> float:
        return self.t_admit_s - self.t_submit_s

    @property
    def service_s(self) -> float:
        """Admission-to-retire residency — the observed data lifetime of
        the request's KV-cache rows."""
        return self.t_retire_s - self.t_admit_s


class ServeEngine:
    def __init__(self, cfg, params, *, n_slots=4, window=512, mesh=None,
                 seed=0, mode="device", decode_chunk=8, top_k_max=64,
                 telemetry=None, clock=None):
        if mode not in ("device", "host"):
            raise ValueError(f"mode must be 'device' or 'host': {mode!r}")
        self.cfg = cfg
        self.model = Model(cfg, mesh=mesh)
        self.params = params
        self.n_slots = n_slots
        self.window = self.model.kv_window(window)
        self.mesh = mesh
        self.mode = mode
        self.decode_chunk = max(1, int(decode_chunk)) if mode == "device" \
            else 1
        self.top_k_max = top_k_max
        # device sampling key (carried through the jitted chunk, split on
        # device); the np rng only feeds the host-mode reference sampler
        # — the two streams intentionally differ (see serving/sampling).
        self.key = jax.random.key(seed)
        self.rng = np.random.default_rng(seed)

        self.cache = self.model.init_cache(n_slots, self.window)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self.active: List[Optional[Request]] = [None] * n_slots
        self.queue: Deque[Request] = deque()
        self.done: List[Request] = []
        self.host_syncs = 0       # all blocking device->host transfers
        self.admit_syncs = 0      # ...of which admission (prefill) syncs
        # host-side prediction of per-slot emitted counts INCLUDING
        # in-flight chunks: the device emits exactly min(K, max_new -
        # emitted) tokens per chunk for a live slot, so this is exact
        # (EOS only shortens it), and run() can skip dispatching chunks
        # in which every slot would sit frozen.
        self._pred = [0] * n_slots

        # --- runtime observability (repro.runtime) ------------------
        # `telemetry` is duck-typed (TelemetryCollector-shaped); its
        # hooks receive only host-side data — see module docstring.
        # The clock defaults to the collector's (virtual clocks make
        # replays deterministic), else wall time.
        self.telemetry = telemetry
        self.clock = clock if clock is not None else \
            (getattr(telemetry, "clock", None) or time.monotonic)
        self.request_log: List[RequestStats] = []
        # host-tracked per-slot context length (KV-cache rows in use),
        # advanced at admission/reconcile — never read from device
        self._ctx = [0] * n_slots

        # per-slot decode-scan state, device resident. Admission touches
        # only the admitted slots via .at[idx].set so updates queue
        # behind any in-flight chunk instead of overwriting its outputs.
        self.last_tok = jnp.zeros((n_slots, 1), jnp.int32)
        self.emitted = jnp.zeros((n_slots,), jnp.int32)
        self.done_mask = jnp.ones((n_slots,), bool)
        self._temp_d = jnp.zeros((n_slots,), jnp.float32)
        self._topk_d = jnp.ones((n_slots,), jnp.int32)
        self._maxnew_d = jnp.zeros((n_slots,), jnp.int32)
        self._eos_d = jnp.full((n_slots,), -1, jnp.int32)
        # host-mode mirror of the feedback tokens: host sampling fills it
        # slot by slot, then ONE upload per step refreshes the device
        # copy. Snapshotted (np.array copy) on upload: jnp.asarray may
        # alias host memory on CPU, and mutating an aliased buffer is UB.
        self._tok_np = np.zeros((n_slots, 1), np.int32)

        # --- compiled programs --------------------------------------
        self._prefill_logits = jax.jit(
            lambda p, b: self.model.prefill(p, b, W=self.window))
        self._decode = jax.jit(self.model.decode_step)      # host mode

        def _admit_kernel(p, batch, cache, pos, last_tok, emitted, done,
                          temp, topk, max_new, eos, meta_i, r_temp, key):
            """Fused admission: batched prefill + first-token sampling +
            cache-row insertion + slot-state scatter, ONE dispatch per
            prompt-length group. meta_i is (4, Bp) int32 rows (slot idx,
            top_k, max_new, eos); pad rows carry idx == n_slots, which is
            out of bounds and therefore DROPPED by JAX scatter semantics,
            so bucket padding never touches a live slot."""
            idx, r_topk, r_maxnew, r_eos = meta_i
            key, sub = jax.random.split(key)
            if self.cfg.attn_kind == "mla":     # rows written in place
                logits, cache, rpos = self.model.prefill(
                    p, batch, W=self.window, into=(cache, idx))
                rows = None
            else:
                logits, rows, rpos = self.model.prefill(p, batch,
                                                        W=self.window)
            tok = sample_tokens(logits, sub, r_temp, r_topk,
                                k_max=self.top_k_max)
            fin = (r_maxnew <= 1) | ((r_eos >= 0) & (tok == r_eos))
            if rows is not None:
                cache = jax.tree.map(
                    lambda c, rc: c.at[:, idx].set(rc.astype(c.dtype)),
                    cache, rows)
            pos = pos.at[idx].set(rpos)
            last_tok = last_tok.at[idx, 0].set(tok)
            emitted = emitted.at[idx].set(1)
            done = done.at[idx].set(fin)
            temp = temp.at[idx].set(r_temp)
            topk = topk.at[idx].set(r_topk)
            max_new = max_new.at[idx].set(r_maxnew)
            eos = eos.at[idx].set(r_eos)
            return (tok, cache, pos, last_tok, emitted, done, temp, topk,
                    max_new, eos, key)

        self._admit_device = jax.jit(
            _admit_kernel, donate_argnums=tuple(range(2, 11)) + (13,))

        def _chunk(p, cache, token, pos, emitted, done, temp, topk,
                   max_new, eos, key):
            samp = lambda lg, k: sample_tokens(lg, k, temp, topk,
                                               k_max=self.top_k_max)
            keys = jax.random.split(key, self.decode_chunk + 1)
            out = self.model.decode_loop(
                p, cache, token, pos, emitted, max_new, done, eos, samp,
                keys[1:], n_tokens=self.decode_chunk)
            return out + (keys[0],)

        # donate the scan carry: cache/token/pos/emitted/done and the
        # PRNG key are replaced by the returned arrays every chunk, so
        # their buffers are reused in place (no KV-cache round-trip).
        self._decode_chunk = jax.jit(_chunk,
                                     donate_argnums=(1, 2, 3, 4, 5, 10))

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if (self.mode == "device" and req.temperature > 0
                and req.top_k > self.top_k_max):
            warnings.warn(
                f"request {req.rid}: top_k={req.top_k} exceeds the "
                f"engine's static top_k_max={self.top_k_max}; device "
                f"sampling draws from the top {self.top_k_max} candidates "
                f"only (host mode would use the full top_k) — raise "
                f"ServeEngine(top_k_max=...) for wider sampling")
        req.out_tokens = []
        req.t_submit_s = self.clock()
        self.queue.append(req)
        if self.telemetry is not None:
            self.telemetry.on_submit(req.rid, len(req.prompt),
                                     len(self.queue))

    def _free_slots(self):
        return [i for i, r in enumerate(self.active) if r is None]

    # ------------------------------------------------------------------
    # admission: length-grouped, batch-bucketed prefill
    # ------------------------------------------------------------------
    def _admit(self):
        free = self._free_slots()
        if not free or not self.queue:
            return
        take = []
        for slot in free:
            if not self.queue:
                break
            take.append((slot, self.queue.popleft()))
        groups = {}
        for slot, req in take:
            groups.setdefault(len(req.prompt), []).append((slot, req))
        for p_len, items in groups.items():
            with trace.span("serve.admit") as sp:
                if sp is not None:
                    bucket = 1 << (len(items) - 1).bit_length()
                    sp.attrs.update(rows=len(items) * p_len,
                                    padded_rows=bucket * p_len)
                self._admit_group(items)
        if self.mode == "host":
            self.last_tok = jnp.asarray(np.array(self._tok_np))

    def _prefill_batch(self, toks):
        Bp = toks.shape[0]
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (Bp, self.cfg.enc_frames, self.cfg.d_model), jnp.bfloat16)
        if self.cfg.family == "vlm":
            batch["patches"] = jnp.zeros(
                (Bp, self.cfg.n_patches, self.cfg.d_model), jnp.bfloat16)
        return batch

    def _admit_group(self, items):
        """One prefill dispatch for equal-length prompts, batch padded to
        a power-of-two bucket (edge-repeat) for program reuse."""
        B = len(items)
        toks = np.stack([r.prompt for _, r in items]).astype(np.int32)
        Bp = 1 << (B - 1).bit_length()
        if Bp > B:
            toks = np.concatenate(
                [toks, np.repeat(toks[-1:], Bp - B, axis=0)])
        batch = self._prefill_batch(toks)

        if self.mode == "device":
            # (idx, top_k, max_new, eos) packed into one int32 upload;
            # pad rows get idx == n_slots (out of bounds -> dropped)
            meta_i = np.full((4, Bp), -1, np.int32)
            meta_i[0] = self.n_slots
            meta_i[2] = 1
            temp = np.zeros((Bp,), np.float32)
            for i, (s, r) in enumerate(items):
                meta_i[0, i] = s
                meta_i[1, i] = r.top_k
                meta_i[2, i] = r.max_new_tokens
                meta_i[3, i] = -1 if r.eos_id is None else r.eos_id
                temp[i] = r.temperature
            (tok_d, self.cache, self.pos, self.last_tok, self.emitted,
             self.done_mask, self._temp_d, self._topk_d, self._maxnew_d,
             self._eos_d, self.key) = self._admit_device(
                self.params, batch, self.cache, self.pos, self.last_tok,
                self.emitted, self.done_mask, self._temp_d, self._topk_d,
                self._maxnew_d, self._eos_d, jnp.asarray(meta_i),
                jnp.asarray(temp), self.key)
            first = np.asarray(jax.device_get(tok_d))[:B]
            self.host_syncs += 1
            self.admit_syncs += 1
            self._record_first_tokens(items, first)
            return

        logits, cache_g, _ = self._prefill_logits(self.params, batch)
        logits_np = np.asarray(jax.device_get(logits), np.float32)
        self.host_syncs += 1
        self.admit_syncs += 1
        first = np.array(
            [sample_host(logits_np[i], r.temperature, r.top_k, self.rng)
             for i, (_, r) in enumerate(items)], np.int32)

        idx = jnp.asarray(np.array([s for s, _ in items], np.int32))

        def put(c, rc):
            return c.at[:, idx].set(rc[:, :B].astype(c.dtype))
        self.cache = jax.tree.map(put, self.cache, cache_g)
        # prefill pos == sequence length fed to the backbone (vlm
        # prepends patch embeds) — computed host-side to avoid a sync
        S = toks.shape[1] + (self.cfg.n_patches
                             if self.cfg.family == "vlm" else 0)
        self.pos = self.pos.at[idx].set(S)
        self._record_first_tokens(items, first)

    def _record_first_tokens(self, items, first):
        """Shared admission bookkeeping: record each request's prefill
        token, retire requests that finish at prefill (max_new <= 1 or
        EOS — the device kernel computes the matching `fin` flag), and
        activate the rest. Both modes MUST run this identically for the
        cross-mode greedy-parity contract to hold."""
        now = self.clock()
        for i, (slot, req) in enumerate(items):
            t = int(first[i])
            req.out_tokens.append(t)
            req.t_admit_s = now
            if (len(req.out_tokens) >= req.max_new_tokens
                    or (req.eos_id is not None and t == req.eos_id)):
                self.done.append(req)      # finished at prefill
                self._log_done(req, now)
                continue
            self.active[slot] = req
            # prefill writes one cache row per backbone position (vlm
            # prepends patch embeds) — same formula as the host-mode pos
            self._ctx[slot] = len(req.prompt) + \
                (self.cfg.n_patches if self.cfg.family == "vlm" else 0)
            self._tok_np[slot, 0] = t
            self._pred[slot] = 1
        if self.telemetry is not None:
            self.telemetry.on_admit(
                len(items), sum(len(r.prompt) for _, r in items),
                len(self.queue))

    def _log_done(self, req, now):
        fallback = lambda t: t if t is not None else now
        st = RequestStats(req.rid, len(req.prompt), len(req.out_tokens),
                          fallback(req.t_submit_s), fallback(req.t_admit_s),
                          fallback(req.t_admit_s), now)
        self.request_log.append(st)
        if self.telemetry is not None:
            self.telemetry.on_retire(st)

    def _retire(self, slot):
        req = self.active[slot]
        self.active[slot] = None
        self._ctx[slot] = 0
        self.done.append(req)
        self._log_done(req, self.clock())

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def _dispatch_chunk(self):
        """Launch one fused K-token decode; returns the (K, slots) token,
        live-mask and held-assignment (MoE, else None) device arrays
        WITHOUT syncing."""
        with trace.span("serve.chunk"):
            (self.cache, self.last_tok, self.pos, self.emitted,
             self.done_mask, toks, live, held, self.key) = \
                self._decode_chunk(
                    self.params, self.cache, self.last_tok, self.pos,
                    self.emitted, self.done_mask, self._temp_d,
                    self._topk_d, self._maxnew_d, self._eos_d, self.key)
        for slot, req in enumerate(self.active):
            if req is not None:
                self._pred[slot] = min(self._pred[slot] + self.decode_chunk,
                                       req.max_new_tokens)
        return toks, live, held, list(self.active)

    def _reconcile(self, toks, live, held, snapshot):
        """Fold a (K, slots) chunk back into the request streams recorded
        at dispatch time and retire finished slots (one blocking sync)."""
        toks, live, held = jax.device_get((toks, live, held))
        self.host_syncs += 1
        toks, live = np.asarray(toks), np.asarray(live)
        trace.count("serve.slot_steps", live.size)
        trace.count("serve.slot_steps_live", int(live.sum()))
        if held is not None:
            trace.count("moe.held_assignments",
                        int((np.asarray(held) * live).sum()))
        if self.telemetry is not None:
            # the done mask freezes monotonically inside a chunk, so
            # per-slot emitted counts are the live-mask column sums —
            # already on host, no extra sync. The hook runs BEFORE the
            # retire loop so a virtual clock has advanced past this
            # chunk when retire timestamps are stamped.
            em = live.sum(axis=0)
            rows = [min(self._ctx[s]
                        + (int(em[s]) if self.active[s] is r else 0),
                        self.window)
                    for s, r in enumerate(snapshot) if r is not None]
            self.telemetry.on_chunk(
                toks.shape[0],
                int(sum(int(em[s]) for s, r in enumerate(snapshot)
                        if r is not None)),
                rows, len(self.queue))
        for slot, req in enumerate(snapshot):
            if req is None:
                continue
            n_app = 0
            for k in range(toks.shape[0]):
                if not live[k, slot]:
                    break                 # slot froze earlier in the chunk
                req.out_tokens.append(int(toks[k, slot]))
                n_app += 1
            if self.active[slot] is not req:
                continue                  # slot re-admitted since dispatch
            self._ctx[slot] = min(self._ctx[slot] + n_app, self.window)
            self._tok_np[slot, 0] = req.out_tokens[-1]
            if (len(req.out_tokens) >= req.max_new_tokens
                    or (req.eos_id is not None
                        and req.out_tokens[-1] == req.eos_id)):
                self._retire(slot)

    def _may_emit(self):
        """Host-side prediction of whether any slot can still produce
        tokens (EOS hits are only discovered at reconcile)."""
        return any(r is not None and self._pred[s] < r.max_new_tokens
                   for s, r in enumerate(self.active))

    def step(self):
        """One synchronous engine iteration: admit waiting requests, then
        one decode dispatch — `decode_chunk` fused tokens (device mode)
        or a single token (host mode) — and reconcile."""
        self._admit()
        # a whole admission wave can finish at prefill (max_new <= 1 /
        # instant EOS) without occupying a slot — keep draining the
        # queue rather than stranding it behind an idle engine
        while all(r is None for r in self.active) and self.queue:
            self._admit()
        if all(r is None for r in self.active):
            return False
        if self.mode == "host":
            return self._step_host()
        self._reconcile(*self._dispatch_chunk())
        return True

    def _step_host(self):
        """The pre-device-resident loop: one decode_step, logits pulled
        to host, np-rng sampling per slot. Kept as parity reference."""
        logits, self.cache = self._decode(self.params, self.cache,
                                          self.last_tok, self.pos)
        self.pos = self.pos + 1
        logits_np = np.asarray(jax.device_get(logits), np.float32)
        self.host_syncs += 1
        if self.telemetry is not None:
            rows = [min(self._ctx[s] + 1, self.window)
                    for s, r in enumerate(self.active) if r is not None]
            self.telemetry.on_chunk(1, len(rows), rows, len(self.queue))
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            tok = sample_host(logits_np[slot], req.temperature, req.top_k,
                              self.rng)
            req.out_tokens.append(tok)
            self._ctx[slot] = min(self._ctx[slot] + 1, self.window)
            self._tok_np[slot, 0] = tok
            if (len(req.out_tokens) >= req.max_new_tokens
                    or (req.eos_id is not None and tok == req.eos_id)):
                self._retire(slot)
        self.last_tok = jnp.asarray(np.array(self._tok_np))
        return True

    def run(self, max_steps=10000):
        """Serve until queue and slots drain. Device mode pipelines: the
        next chunk is dispatched before the previous chunk's tokens are
        pulled, so reconcile/admit/prefill run while the device decodes
        (a freed slot rejoins one chunk later)."""
        steps = 0
        if self.mode == "host":
            while (self.queue or any(r is not None for r in self.active)) \
                    and steps < max_steps:
                self.step()
                steps += 1
            return self.done, steps
        pending = None
        while steps < max_steps:
            if pending is None:
                self._admit()   # nothing in flight: admit synchronously
                # requests can finish AT prefill without occupying a
                # slot; keep admitting so the queue is never stranded
                while not self._may_emit() and self.queue:
                    self._admit()
                if not self._may_emit():
                    break
            nxt = self._dispatch_chunk() if self._may_emit() else None
            if pending is not None:
                self._reconcile(*pending)
            self._admit()       # freed slots rejoin at the NEXT chunk
            pending = nxt
            steps += 1
        if pending is not None:
            self._reconcile(*pending)
        return self.done, steps
