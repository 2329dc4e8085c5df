"""End-to-end behaviour test of the paper's system: fine-tune a reduced
BERT on a synthetic CLUE-like task, calibrate, sweep the SAMP grid, and
check the qualitative claims of Table 2 hold:

  * trained accuracy is far above chance (the task carries signal),
  * quantized configs keep finite, sane accuracy,
  * the allocator recommends a non-float config with bounded accuracy drop,
  * Fully-Quant degrades at least as much as Quant-FFN-Only (Appendix B).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.core.samp import SAMPEngine
from repro.data import eval_accuracy, get_batch, make_task
from repro.models import transformer as T
from repro.train import AdamW, TrainConfig, Trainer

pytestmark = pytest.mark.system   # excluded from the fast CI subset

KEY = jax.random.PRNGKey(0)
N_CLASSES = 5


@pytest.fixture(scope="module")
def finetuned():
    cfg = get_config("bert-base").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    task = make_task("tnews", vocab_size=cfg.vocab_size, seq_len=24)
    task = task.__class__(**{**task.__dict__, "n_classes": N_CLASSES})
    tcfg = TrainConfig(steps=120, log_every=1000, compute_dtype="float32",
                       remat=False)
    tr = Trainer(cfg, policy, optimizer=AdamW(lr=2e-3), tcfg=tcfg,
                 head=("cls", N_CLASSES))
    state = tr.init_state(KEY)
    step = tr.make_step()
    from repro.train.trainer import TrainState
    for i in range(tcfg.steps):
        b = get_batch(task, i, 32)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "segments": jnp.asarray(b["segments"]),
                 "labels": jnp.asarray(b["labels"])}
        p, o, e, m = step(state.params, state.opt_state, state.err_state,
                          batch)
        state = TrainState(p, o, e)
    return cfg, task, state.params


def _predict_fn(cfg, plan, params):
    @jax.jit
    def fwd(tokens, segments):
        hidden, _ = T.forward(params, {"tokens": tokens,
                                       "segments": segments},
                              cfg, plan, compute_dtype=jnp.float32)
        return jnp.argmax(T.apply_head(hidden, params, "cls"), -1)

    def predict(batch):
        return fwd(jnp.asarray(batch["tokens"]),
                   jnp.asarray(batch["segments"]))
    return predict


def test_finetuned_beats_chance(finetuned):
    cfg, task, params = finetuned
    eng = SAMPEngine(cfg, float_dtype="float32")
    acc = eval_accuracy(_predict_fn(cfg, eng.float_plan, params), task,
                        batches=4, batch_size=32)
    assert acc > 2.0 / N_CLASSES          # way above 0.2 chance


def test_samp_sweep_and_allocator(finetuned):
    cfg, task, params = finetuned
    eng = SAMPEngine(cfg, float_dtype="float32")
    calib = [{"tokens": jnp.asarray(b["tokens"]),
              "segments": jnp.asarray(b["segments"])}
             for b in (get_batch(task, i, 16) for i in range(3))]
    stats = eng.calibrate(params, calib)

    def eval_fn(qp, plan, policy):
        return eval_accuracy(_predict_fn(cfg, plan, qp), task,
                             batches=3, batch_size=32)

    def latency_fn(qp, plan, policy):
        # analytic roofline latency model (per-layer GEMM precision)
        from repro.toolkit.latency import encoder_latency
        return encoder_latency(cfg, policy, batch=32, seq=24)

    pts = eng.sweep(params, stats, eval_fn, latency_fn, stride=4)
    base = pts[0]
    assert base.mode_name == "float"
    by_mode = {}
    for p in pts[1:]:
        by_mode.setdefault(p.mode_name, []).append(p)
    # latency strictly decreases with more quantized layers (modeled)
    for mode, series in by_mode.items():
        lats = [p.latency for p in sorted(series, key=lambda q: q.k)]
        assert all(b < a for a, b in zip(lats, lats[1:]))
    # the paper's qualitative claim: at full depth, FFN-only >= fully-quant
    full_k = cfg.num_layers
    acc_ffn = [p for p in by_mode["quant_ffn_only"] if p.k == full_k]
    acc_ful = [p for p in by_mode["fully_quant"] if p.k == full_k]
    if acc_ffn and acc_ful:
        assert acc_ffn[0].accuracy >= acc_ful[0].accuracy - 0.05
    # allocator: recommendation exists, drops bounded, speedup real
    recs = eng.recommend(pts)
    for r in recs:
        assert r.recommendation.speedup >= 1.0
        assert r.point.accuracy >= 0  # finite & sane
    # threshold modes behave
    rec_lat = eng.recommend(pts, max_latency=base.latency * 0.9)
    for r in rec_lat:
        assert r.point.latency <= base.latency * 0.9 + 1e-9
