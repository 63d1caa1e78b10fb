"""Production serving launcher: loads (or initializes) params, starts the
slot-based continuous-batching engine, and serves a synthetic request
stream (or stdin token prompts). Decode is device-resident by default:
`--decode-chunk K` fuses K decode+sample steps per host dispatch (one
host sync per K tokens); `--host-loop` falls back to the per-token
reference loop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \\
        --slots 4 --window 1024 --decode-chunk 8 [--host-loop] \\
        [--reduced] [--ckpt-dir /ckpt/run1]
"""
from __future__ import annotations

import argparse
import dataclasses
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="tokens generated per host dispatch (device mode)")
    ap.add_argument("--host-loop", action="store_true",
                    help="per-token host sampling loop (parity reference)")
    ap.add_argument("--kv-dtype", default=None, choices=[None, "int8"])
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore trained params from a checkpoint dir")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--stats", action="store_true",
                    help="attach the runtime telemetry collector and print "
                         "the window summary + per-request log")
    args = ap.parse_args()

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import Model
    from repro.serving import ServeEngine
    from repro.serving.engine import Request

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), name=cfg.name,
                                  dtype="float32")
    if args.kv_dtype:
        cfg = dataclasses.replace(cfg, kv_dtype=args.kv_dtype)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        cm = CheckpointManager(args.ckpt_dir)
        step, restored = cm.restore_latest(
            jax.eval_shape(model.init, jax.random.key(0)))
        if restored is not None:
            # serving uses the master params cast to the compute dtype
            params = jax.tree.map(lambda a, s: a.astype(s.dtype), restored,
                                  jax.eval_shape(model.init,
                                                 jax.random.key(0)))
            print(f"restored params from step {step}")

    collector = None
    if args.stats:
        from repro.runtime import TelemetryCollector
        collector = TelemetryCollector()        # wall clock
    eng = ServeEngine(cfg, params, n_slots=args.slots, window=args.window,
                      mode="host" if args.host_loop else "device",
                      decode_chunk=args.decode_chunk, telemetry=collector)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size,
                                rng.integers(4, 32)).astype(np.int32),
            max_new_tokens=args.max_new, temperature=0.7 if i % 2 else 0.0))
    t0 = time.time()
    done, steps = eng.run()
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    mode = "host-loop" if args.host_loop else \
        f"device chunk={eng.decode_chunk}"
    print(f"served {len(done)} requests / {toks} tokens in {steps} engine "
          f"steps / {dt:.2f}s ({toks/max(dt,1e-9):.1f} tok/s, "
          f"{eng.host_syncs} host syncs = "
          f"{toks/max(eng.host_syncs,1):.1f} tok/sync, {mode})")
    if collector is not None:
        win = collector.snapshot()
        print(f"[telemetry] {win.decode_steps} decode steps, "
              f"mean batch {win.mean_batch:.2f}, "
              f"mean KV rows {win.mean_kv_rows:.1f}, "
              f"mean queue depth {win.mean_queue_depth:.2f}, "
              f"{win.prefill_tokens} prefill + {win.decode_tokens} decode "
              f"tokens over {win.duration_s:.2f}s")
        print(f"{'rid':>5} {'prompt':>7} {'emitted':>8} "
              f"{'queue_wait_s':>13} {'service_s':>10}")
        for st in sorted(eng.request_log, key=lambda s: s.rid):
            print(f"{st.rid:>5} {st.prompt_len:>7} {st.emitted:>8} "
                  f"{st.queue_wait_s:>13.4f} {st.service_s:>10.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
