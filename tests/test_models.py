"""Per-arch smoke tests (deliverable f): reduced config, one forward/train
step on CPU, output shapes + finiteness; decode-vs-prefill consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core.plan import PrecisionPlan
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, S=16):
    batch = {}
    if cfg.frontend == "audio":
        batch["frames"] = jax.random.normal(KEY, (B, S, cfg.frontend_dim))
        batch["labels"] = jnp.zeros((B, S), jnp.int32)
        return batch
    batch["tokens"] = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    if cfg.frontend == "vision":
        batch["prefix_embeds"] = jax.random.normal(
            KEY, (B, cfg.num_prefix_embeds, cfg.frontend_dim))
    if cfg.family == "bert":
        batch["segments"] = jnp.zeros((B, S), jnp.int32)
        batch["labels"] = jnp.zeros((B,), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_config(arch).reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    head = ("cls", 5) if cfg.family == "bert" else None
    params = T.init_params(KEY, cfg, policy, head=head)
    batch = make_batch(cfg)
    out, _ = T.forward(params, batch, cfg, plan, compute_dtype=jnp.float32,
                       chunk=8)
    B = 2
    S_out = 16 + (cfg.num_prefix_embeds if cfg.frontend == "vision" else 0)
    want_dim = cfg.d_model if head else cfg.vocab_size
    assert out.shape == (B, S_out, want_dim)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_config(arch).reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    head = ("cls", 5) if cfg.family == "bert" else None
    params = T.init_params(KEY, cfg, policy, head=head)
    batch = make_batch(cfg)
    loss, grads = jax.value_and_grad(
        lambda p: T.lm_loss(p, batch, cfg, plan, remat=True,
                            compute_dtype=jnp.float32))(params)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_config(a).supports_decode
                                  and get_config(a).frontend is None])
def test_decode_matches_prefill(arch):
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:     # avoid capacity-drop divergence
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=16.0))
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    B, S = 2, 10
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full, _ = T.forward(params, {"tokens": toks}, cfg, plan,
                        compute_dtype=jnp.float32, chunk=None)
    caches = T.init_caches(cfg, plan, B, S, jnp.float32)
    outs = []
    for t in range(S):
        lg, caches = T.decode_step(params, toks[:, t:t + 1], caches, t, cfg,
                                   plan, compute_dtype=jnp.float32)
        outs.append(lg[:, 0])
    rel = (float(jnp.max(jnp.abs(jnp.stack(outs, 1) - full)))
           / float(jnp.max(jnp.abs(full))))
    assert rel < 2e-3


def test_prefill_then_decode_continues():
    """Bulk prefill writes caches decode can continue from."""
    cfg = get_config("qwen2-0.5b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    B, S = 2, 8
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab_size)
    # reference: full forward over S+1 tokens
    full, _ = T.forward(params, {"tokens": toks}, cfg, plan,
                        compute_dtype=jnp.float32, chunk=None)
    # prefill S, then decode token S
    caches = T.init_caches(cfg, plan, B, S + 1, jnp.float32)
    _, caches = T.forward(params, {"tokens": toks[:, :S]}, cfg, plan,
                          caches=caches, pos=0, compute_dtype=jnp.float32,
                          chunk=None)
    lg, _ = T.decode_step(params, toks[:, S:S + 1], caches, S, cfg, plan,
                          compute_dtype=jnp.float32)
    rel = (float(jnp.max(jnp.abs(lg[:, 0] - full[:, S])))
           / float(jnp.max(jnp.abs(full))))
    assert rel < 2e-3


def test_sliding_window_ring_buffer_decode():
    """mixtral-style ring cache: decode past the window stays correct."""
    cfg = get_config("mixtral-8x22b").reduced()
    cfg = cfg.replace(sliding_window=4,
                      moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    B, S = 1, 12
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    full, _ = T.forward(params, {"tokens": toks}, cfg, plan,
                        compute_dtype=jnp.float32, chunk=None)
    # ring cache bounded by the window (max_len = S but window = 4)
    caches = T.init_caches(cfg, plan, B, S, jnp.float32)
    # ring buffers should be window-sized, not S-sized
    kv_leaf = jax.tree_util.tree_leaves(caches)[0]
    outs = []
    for t in range(S):
        lg, caches = T.decode_step(params, toks[:, t:t + 1], caches, t, cfg,
                                   plan, compute_dtype=jnp.float32)
        outs.append(lg[:, 0])
    rel = (float(jnp.max(jnp.abs(jnp.stack(outs, 1) - full)))
           / float(jnp.max(jnp.abs(full))))
    assert rel < 2e-3


def test_chunked_attention_matches_unchunked():
    cfg = get_config("gemma2-2b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    toks = jax.random.randint(KEY, (2, 32), 0, cfg.vocab_size)
    a, _ = T.forward(params, {"tokens": toks}, cfg, plan,
                     compute_dtype=jnp.float32, chunk=None)
    b, _ = T.forward(params, {"tokens": toks}, cfg, plan,
                     compute_dtype=jnp.float32, chunk=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


def test_repack_roundtrip():
    from repro.core.plan import LayerMode
    cfg = get_config("gemma2-2b").reduced()
    fp = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan_f = T.build_plan(cfg, fp)
    params = T.init_params(KEY, cfg, fp)
    qp_policy = PrecisionPlan.prefix(cfg.num_layers, 2,
                                     LayerMode.QUANT_FFN_ONLY, "float32")
    plan_q = T.build_plan(cfg, qp_policy)
    repacked = T.repack(params, plan_f, plan_q)
    back = T.repack(repacked, plan_q, plan_f)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
