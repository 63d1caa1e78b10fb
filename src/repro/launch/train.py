"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \\
        --shape train_4k --steps 1000 --ckpt-dir /ckpt/run1 \\
        [--mesh 16x16 | --mesh 2x16x16] [--microbatches 4] [--reduced]

On real hardware the mesh axes map onto the fleet via jax.distributed
(initialize() is called when JAX_COORDINATOR is set); on this CPU
container use --reduced --mesh 1x1 for a functional end-to-end run.
Restarting the same command resumes from the newest committed checkpoint
(elastic: the mesh may differ between runs).
"""
from __future__ import annotations

import argparse
import dataclasses
import os


def parse_mesh(spec: str):
    from repro.launch.mesh import auto_mesh
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 3:
        return auto_mesh(dims, ("pod", "data", "model"))
    if len(dims) == 2:
        return auto_mesh(dims, ("data", "model"))
    return auto_mesh(dims, ("data",))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 16x16 or 2x16x16; default: all devices as data")
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU bring-up)")
    args = ap.parse_args()

    if os.environ.get("JAX_COORDINATOR"):
        import jax
        jax.distributed.initialize()

    import jax
    from repro.configs import get_config, SHAPES, ShapeConfig
    from repro.training import Trainer, TrainConfig

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), name=cfg.name,
                                  dtype="float32")
        shape = ShapeConfig(shape.name, min(shape.seq_len, 128),
                            min(shape.global_batch, 8), shape.kind)
    mesh = parse_mesh(args.mesh) if args.mesh else \
        parse_mesh(str(len(jax.devices())))

    tr = Trainer(cfg, mesh, shape,
                 TrainConfig(total_steps=args.steps,
                             ckpt_every=args.ckpt_every,
                             ckpt_dir=args.ckpt_dir, seed=args.seed,
                             microbatches=args.microbatches))
    state, hist = tr.run()
    if hist:
        print(f"done: step {hist[-1]['step']} loss {hist[-1]['loss']:.4f}; "
              f"stats {tr.stats}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
