"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --steps 100 --batch 8 --seq 256 [--smoke] [--ckpt-dir DIR]

On a real TPU slice this runs under `jax.distributed.initialize()` with the
production mesh; on this CPU container use --smoke (reduced config, host
mesh). The step function is identical to the one the dry-run lowers for the
16x16 / 2x16x16 meshes.
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.dist import context as dctx
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.loop import LoopConfig, train
from repro.train.step import TrainConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(configs.ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--schedule", default="cosine",
                    choices=["cosine", "wsd", "const"])
    args = ap.parse_args()

    cfg = configs.get_config(args.arch)
    if args.smoke:
        cfg = configs.smoke_config(cfg)
        mesh = make_host_mesh()
    else:
        if jax.device_count() < 256:
            raise SystemExit(
                "full configs need the production mesh; run the dry-run for "
                "lowering checks on CPU, or pass --smoke")
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    # MiniCPM trains with WSD per its paper
    sched = "wsd" if (args.arch == "minicpm-2b" and args.schedule == "cosine") \
        else args.schedule
    model = build_model(cfg)
    with dctx.mesh_context(mesh):
        out = train(
            model,
            loop_cfg=LoopConfig(total_steps=args.steps,
                                global_batch=args.batch, seq_len=args.seq,
                                ckpt_dir=args.ckpt_dir, log_every=5),
            train_cfg=TrainConfig(optimizer=AdamWConfig(
                schedule=sched, warmup_steps=max(1, args.steps // 10),
                total_steps=args.steps)),
            log_fn=lambda m: print(
                f"step {m['data_step']:>5} loss {m['loss']:.4f} "
                f"lr {m['lr']:.2e}", flush=True),
        )
    print(f"done; final loss {out['history'][-1]['loss']:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
