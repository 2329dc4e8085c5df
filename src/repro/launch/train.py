"""Training launcher CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --batch 8 --seq 256 --ckpt /tmp/run1

Local runs use the reduced() config on the host mesh; ``--full`` selects the
production config (real-hardware path). Resumes automatically from the
newest checkpoint in --ckpt; survives kill-at-any-step.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.data import get_batch, make_task
from repro.launch.mesh import make_host_mesh
from repro.train import AdamW, TrainConfig, Trainer, cosine_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="production config (default: reduced smoke config)")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "bfloat16")
    mesh = make_host_mesh(model=args.mesh_model) \
        if len(jax.devices()) > 1 else None
    tcfg = TrainConfig(steps=args.steps, checkpoint_dir=args.ckpt,
                       grad_accum=args.grad_accum, remat=True,
                       compute_dtype=args.dtype,
                       compress_pod_grads=args.compress_pod_grads)
    opt = AdamW(lr=cosine_schedule(args.lr, warmup=min(20, args.steps // 10),
                                   total=args.steps))
    trainer = Trainer(cfg, policy, mesh=mesh, optimizer=opt, tcfg=tcfg)
    state = trainer.init_state(jax.random.PRNGKey(args.seed),
                               dtype=jnp.dtype(args.dtype))
    task = make_task("lm", vocab_size=cfg.vocab_size, seq_len=args.seq)

    def next_batch(i):
        b = get_batch(task, i, args.batch)
        if cfg.frontend == "audio":
            g = jax.random.PRNGKey(i)
            frames = jax.random.normal(
                g, (args.batch, args.seq, cfg.frontend_dim),
                jnp.dtype(args.dtype))
            return {"frames": frames,
                    "labels": jnp.asarray(b["tokens"] % cfg.vocab_size)}
        return {k: jnp.asarray(v) for k, v in b.items()}

    trainer.fit(state, next_batch)
    print(f"[train] done: {args.steps} steps of {args.arch}"
          f"{' (reduced)' if not args.full else ''}")


if __name__ == "__main__":
    main()
