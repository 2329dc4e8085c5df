"""Table 2 reproduction: SAMP tradeoff on CLUE-like classification tasks.

The paper fine-tunes BERT-base (L12 H768) on AFQMC/IFLYTEK/TNEWS and sweeps
(mode, k) measuring accuracy + speedup, then underlines the combination the
accuracy-decay-aware allocator recommends. This container has no GPU/CLUE,
so the reproduction keeps the full experimental *structure* at calibration
scale: a width-reduced 12-LAYER BERT (layer count preserved — the k axis is
the paper's object of study) fine-tuned on synthetic stand-ins of the three
tasks; accuracy is genuinely measured on a held-out dev stream, speedup is the
analytic TPU roofline latency model (repro.toolkit.latency — the same
interface wall-clock numbers flow through on hardware).

Emits the Table-2-shaped grid per task with the allocator's underlined
recommendation per mode.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.core.samp import SAMPEngine
from repro.data import eval_accuracy, get_batch, make_task
from repro.models import transformer as T
from repro.toolkit.latency import encoder_latency
from repro.train import AdamW, TrainConfig, Trainer
from repro.train.trainer import TrainState

TASKS = (("afqmc", "afqmc", 2), ("iflytek", "iflytek", 119),
         ("tnews", "tnews", 15))


def finetune(cfg, task, n_classes, steps=150, seed=0):
    head = ("cls", n_classes)
    precision = PrecisionPlan.full_float(cfg.num_layers, "float32")
    tr = Trainer(cfg, precision, optimizer=AdamW(lr=2e-3),
                 tcfg=TrainConfig(steps=steps, log_every=10_000,
                                  compute_dtype="float32", remat=False),
                 head=head)
    state = tr.init_state(jax.random.PRNGKey(seed))
    step = tr.make_step()
    for i in range(steps):
        b = get_batch(task, i, 32)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        p, o, e, _ = step(state.params, state.opt_state, state.err_state,
                          batch)
        state = TrainState(p, o, e)
    return state.params


def predictor(cfg, plan, params):
    @jax.jit
    def fwd(tokens, segments):
        hidden, _ = T.forward(params, {"tokens": tokens,
                                       "segments": segments}, cfg, plan,
                              compute_dtype=jnp.float32)
        return jnp.argmax(T.apply_head(hidden, params, "cls"), -1)

    return lambda b: fwd(jnp.asarray(b["tokens"]), jnp.asarray(b["segments"]))


def run_task(name, task_key, n_classes, *, steps=150, stride=2,
             seq_len=128, emit=print):
    # seq 128: attention probs sit well below 1/127, so symmetric int8
    # softmax quantization bites visibly (the paper's Appendix-B regime)
    if n_classes > 20:
        steps = int(steps * 2.5)     # many-class heads need longer ft
    cfg = get_config("bert-base").reduced().replace(num_layers=12)
    task = make_task(task_key, vocab_size=cfg.vocab_size, seq_len=seq_len)
    task = task.__class__(**{**task.__dict__, "n_classes": n_classes})
    t0 = time.time()
    params = finetune(cfg, task, n_classes, steps=steps)
    eng = SAMPEngine(cfg, float_dtype="float32")
    calib = [{"tokens": jnp.asarray(b["tokens"]),
              "segments": jnp.asarray(b["segments"])}
             for b in (get_batch(task, 1000 + i, 16) for i in range(4))]
    stats = eng.calibrate(params, calib)

    def eval_fn(qp, plan, precision):
        return eval_accuracy(predictor(cfg, plan, qp), task, batches=8,
                             batch_size=64)

    def latency_fn(qp, plan, precision):
        return encoder_latency(cfg, precision, batch=32, seq=seq_len)

    pts = eng.sweep(params, stats, eval_fn, latency_fn, stride=stride)
    base = pts[0]
    recs = {r.mode_name: r.point for r in eng.recommend(pts)}
    emit(f"\n### {name} (BERT-12 reduced, {n_classes} classes, "
         f"{steps} ft steps, {time.time() - t0:.0f}s)")
    emit("| mode | MHA k | FFN k | accuracy | speedup vs float | rec |")
    emit("|---|---|---|---|---|---|")
    rows = []
    for p in pts:
        mha_k = p.k if p.mode_name == "fully_quant" else 0
        ffn_k = p.k if p.mode_name != "float" else 0
        mark = "**<-**" if recs.get(p.mode_name) is p else ""
        emit(f"| {p.mode_name} | {mha_k}/12 | {ffn_k}/12 | "
             f"{p.accuracy:.4f} | {base.latency / p.latency:.4f} | {mark} |")
        rows.append((name, p.mode_name, p.k, p.accuracy,
                     base.latency / p.latency))
    return rows, pts, recs


def main(steps=150, stride=2, emit=print):
    all_rows = []
    for name, key, n in TASKS:
        rows, _, _ = run_task(name, key, n, steps=steps, stride=stride,
                              emit=emit)
        all_rows.extend(rows)
    return all_rows


if __name__ == "__main__":
    main()
