"""Trainer: loss decreases, kill-resume, grad accumulation, compression."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.data import get_batch, make_task
from repro.train import AdamW, TrainConfig, Trainer, cosine_schedule
from repro.train.optimizer import global_norm
from repro.distributed import compression

KEY = jax.random.PRNGKey(0)


def mk_trainer(tmp_path=None, steps=20, grad_accum=1):
    cfg = get_config("qwen2-0.5b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    tcfg = TrainConfig(steps=steps, log_every=100, checkpoint_every=5,
                       checkpoint_dir=str(tmp_path) if tmp_path else None,
                       grad_accum=grad_accum, remat=True,
                       compute_dtype="float32")
    tr = Trainer(cfg, policy, optimizer=AdamW(lr=3e-3), tcfg=tcfg)
    task = make_task("lm", vocab_size=cfg.vocab_size, seq_len=16)
    nb = lambda i: {k: jnp.asarray(v) for k, v in get_batch(task, i, 8).items()}
    return tr, nb


def test_loss_decreases():
    tr, nb = mk_trainer(steps=30)
    state = tr.init_state(KEY)
    step = tr.make_step()
    first = last = None
    for i in range(30):
        p, o, e, m = step(state.params, state.opt_state, state.err_state,
                          nb(i))
        from repro.train.trainer import TrainState
        state = TrainState(p, o, e)
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first - 0.1, (first, last)


def test_kill_and_resume_bitwise(tmp_path):
    # full run
    tr, nb = mk_trainer(tmp_path / "a", steps=10)
    s = tr.init_state(KEY)
    s = tr.fit(s, nb, log=lambda *_: None)
    # interrupted run: 5 steps, then a fresh trainer resumes to 10
    tr1, nb1 = mk_trainer(tmp_path / "b", steps=5)
    s1 = tr1.init_state(KEY)
    s1 = tr1.fit(s1, nb1, log=lambda *_: None)
    tr2, nb2 = mk_trainer(tmp_path / "b", steps=10)
    s2 = tr2.init_state(KEY)            # fresh init; fit() must resume
    s2 = tr2.fit(s2, nb2, log=lambda *_: None)
    for a, b in zip(jax.tree_util.tree_leaves(s.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_grad_accum_matches_big_batch():
    cfg = get_config("qwen2-0.5b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    task = make_task("lm", vocab_size=cfg.vocab_size, seq_len=16)
    batch = {k: jnp.asarray(v) for k, v in get_batch(task, 0, 8).items()}

    def one_step(accum):
        tcfg = TrainConfig(steps=1, grad_accum=accum, remat=False,
                           compute_dtype="float32")
        tr = Trainer(cfg, policy, optimizer=AdamW(lr=1e-3), tcfg=tcfg)
        state = tr.init_state(KEY)
        step = tr.make_step(jit=False)
        p, _, _, m = step(state.params, state.opt_state, None, batch)
        return p, float(m["loss"])

    p1, l1 = one_step(1)
    p2, l2 = one_step(2)
    assert l1 == pytest.approx(l2, rel=1e-5)
    # On the very first Adam step u = m/(sqrt(v)+eps) ~ sign(g), so f32
    # reduction-order noise in tiny grads is amplified to O(lr) in the
    # update; tolerance is a fraction of lr=1e-3, not of the grads.
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_straggler_monitor_logs():
    tr, _ = mk_trainer(steps=1)
    msgs = []
    for _ in range(12):
        tr._note_step_time(0.01, 1, msgs.append)
    tr._note_step_time(0.2, 13, msgs.append)
    assert any("STRAGGLER" in m for m in msgs)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(jnp.int32(0))) == 0.0
    assert float(lr(jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr(jnp.int32(100))) == pytest.approx(0.1, abs=1e-3)
    assert float(lr(jnp.int32(55))) > float(lr(jnp.int32(90)))


def test_error_feedback_compression_unbiased():
    """int8 grad compression with error feedback: the *accumulated* update
    over steps converges to the true sum (error is carried, not lost)."""
    rng = np.random.RandomState(0)
    g_true = jnp.asarray(rng.randn(64).astype(np.float32)) * 1e-3
    err = jnp.zeros_like(g_true)
    total = jnp.zeros_like(g_true)
    from repro.core.quantize import compute_scale_symmetric
    for _ in range(50):
        gf = g_true + err
        scale = compute_scale_symmetric(jnp.max(jnp.abs(gf)))
        q = jnp.clip(jnp.round(gf / scale), -128, 127)
        deq = q * scale
        err = gf - deq
        total = total + deq
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(g_true * 50),
                               rtol=0.02, atol=1e-5)
