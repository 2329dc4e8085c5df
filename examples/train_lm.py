"""End-to-end training driver: a ~125M-class LM for a few hundred steps with
checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_lm.py [--arch xlstm-125m] \
        [--steps 300] [--ckpt /tmp/lm_run]

Kill it at any step and re-run the same command — it resumes from the newest
atomic checkpoint and fast-forwards the counter-indexed data stream. On a
multi-device host it shards with the FSDP+TP rules automatically.
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.data import get_batch, make_task
from repro.launch.mesh import make_host_mesh
from repro.train import AdamW, TrainConfig, Trainer, cosine_schedule

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="xlstm-125m")
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=64)
ap.add_argument("--ckpt", default="/tmp/repro_lm_run")
ap.add_argument("--full", action="store_true",
                help="full config (TPU path); default = reduced smoke config")
args = ap.parse_args()

cfg = get_config(args.arch)
if not args.full:
    cfg = cfg.reduced()
policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
mesh = make_host_mesh() if len(jax.devices()) > 1 else None
trainer = Trainer(
    cfg, policy, mesh=mesh,
    optimizer=AdamW(lr=cosine_schedule(3e-3, warmup=20, total=args.steps)),
    tcfg=TrainConfig(steps=args.steps, log_every=20, checkpoint_every=50,
                     checkpoint_dir=args.ckpt, compute_dtype="float32",
                     remat=True))
state = trainer.init_state(jax.random.PRNGKey(0))
task = make_task("lm", vocab_size=cfg.vocab_size, seq_len=args.seq)
state = trainer.fit(
    state, lambda i: {k: jnp.asarray(v)
                      for k, v in get_batch(task, i, args.batch).items()})
print(f"done: {args.steps} steps of {args.arch}"
      f"{'' if args.full else ' (reduced)'}; checkpoints in {args.ckpt}")
