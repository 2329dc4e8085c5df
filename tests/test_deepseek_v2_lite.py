"""deepseek-v2-lite and its expert-parallel share, at reduced() widths on
seeded random weights: serving through the engine against the benchmark's
plain reference, the held share, the softmax router, dropless dispatch,
YaRN rope, the routed-row counters, and the MLA page scheme."""
import dataclasses
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.calibration import synthetic_calibration_batches
from repro.core.plan import PrecisionPlan
from repro.core.precision import make_policy
from repro.core.samp import SAMPEngine
from repro.models import layers as L
from repro.models import transformer as T
from repro.serve import Request, ServeEngine

KEY = jax.random.PRNGKey(0)
REFERENCE = (pathlib.Path(__file__).resolve().parents[1] / "bench"
             / "configs" / "deepseek-v2-lite-ep4-samp.py")
PROMPTS = ([5, 17, 3, 99, 42, 7], [11, 2], [64, 65, 66, 67])
#: wider than reduced() (d_model 64), as the benchmark's CPU rehearsal
#: widens it, so that int8 rounding moves the outputs about as little,
#: relative to the int4 control, as at the published widths
WIDE = dict(num_layers=2, d_model=256, num_heads=4, head_dim=64, d_ff=1024)


def _reference():
    spec = importlib.util.spec_from_file_location("dsv2_reference",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _served(cfg, plan_name: str, backend: str):
    """(float params, the served engine's (prompt+output, logits) per
    request): three requests through a paged ServeEngine, the logits of
    every tick of every slot recorded as each decode step returns them."""
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)
    precision = make_policy(cfg, plan_name, float_dtype="float32")
    stats = eng.calibrate(params, synthetic_calibration_batches(cfg),
                          precision=precision)
    qparams, plan = eng.apply(params, stats, precision)
    seen: dict = {}
    engine = ServeEngine(cfg, qparams, plan, batch_slots=2, max_len=32,
                         page_size=4, kv_cache="float", backend=backend,
                         precision=precision)
    inner = engine._decode

    def recording(params, caches, tokens, pos, active, pages=None):
        logits, *rest = inner(params, caches, tokens, pos, active, pages)
        got = np.asarray(logits)
        for s, req in enumerate(engine.sched.active):
            if req is not None and active[s]:
                seen.setdefault(req.uid, {})[
                    int(engine.sched.cursor[s])] = got[s]
        return (logits, *rest)
    engine._decode = recording
    for uid, prompt in enumerate(PROMPTS):
        engine.submit(Request(uid=uid, prompt=list(prompt), max_tokens=5))
    done = engine.run()
    assert len(done) == len(PROMPTS)
    out = []
    for req in sorted(done, key=lambda r: r.uid):
        history = list(req.prompt) + list(req.output)
        rows = sorted(seen[req.uid])
        out.append((history, rows, np.stack([seen[req.uid][r]
                                             for r in rows])))
    return params, out, engine


@pytest.mark.parametrize("plan_name,backend,tol", [
    # float weights: the absorbed decode through float pages against the
    # expanded full forward differs only by float32 summation order
    ("float", "reference", 1e-4),
    ("float", "fused", 1e-4),
    # the plan's int8 dense, shared and routed expert GEMMs against the
    # float32 reference: their rounding, and the near-tied router picks
    # it moves, read up to 0.044 of the logit range here; the int4
    # control reads 0.28-0.44 (asserted below the tolerance's other side)
    ("ffn", "reference", 0.1),
    ("ffn", "fused", 0.1),
])
def test_served_logits_match_the_reference(plan_name, backend, tol):
    """Prefill and decode through the paged cache, logits of every served
    position against the plain reference's full forward of the history,
    as the largest difference over the largest reference logit; for the
    int8 plan, the reference's int4 control must fail the same bound."""
    cfg = get_config("deepseek-v2-lite-ep4").reduced()
    if plan_name == "ffn":
        cfg = cfg.replace(**WIDE)
    ref = _reference()
    params, served, _ = _served(cfg, plan_name, backend)
    fn = jax.jit(lambda p, t, r, c: ref.logits_at(
        p, t, r, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        control=c), static_argnums=3)
    controls = []
    for history, rows, got in served:
        args = (params, jnp.asarray(history, jnp.int32),
                jnp.asarray(rows, jnp.int32))
        want = np.asarray(fn(*args, None))
        scale = np.abs(want).max()
        err = np.abs(got - want).max() / scale
        assert err < tol, (plan_name, backend, err)
        if plan_name == "ffn":
            controls.append(np.abs(np.asarray(fn(*args, "int4"))
                                   - want).max() / scale)
    assert not controls or min(controls) > tol


def test_fused_int8_serving_matches_reference_int8_serving():
    """Same int8 plan, two backends: the grouped expert kernel and
    quant_linear against the XLA int8 path, logit for logit."""
    cfg = get_config("deepseek-v2-lite-ep4").reduced().replace(**WIDE)
    _, ref, _ = _served(cfg, "ffn", "reference")
    _, fused, _ = _served(cfg, "ffn", "fused")
    for (h1, r1, l1), (h2, r2, l2) in zip(ref, fused):
        assert h1 == h2 and r1 == r2
        np.testing.assert_allclose(l2, l1, rtol=0, atol=1e-4 * np.abs(
            l1).max())


def _moe_cfg(count, capacity=None):
    cfg = get_config("deepseek-v2-lite").reduced()
    return cfg.replace(moe=dataclasses.replace(
        cfg.moe, held_count=count, capacity_factor=capacity))


def test_held_shares_add_up_to_the_uncut_layer():
    """Over the four 2-expert shares of an 8-expert layer, the routed parts
    summed, plus the shared experts once, equal the layer that holds all
    eight. A share holds its layer's first experts, so the share of
    experts ``[first, first + 2)`` is the layer whose router columns are
    rolled to put them first."""
    full = _moe_cfg(None)
    p = L.init_moe(KEY, full)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, full.d_model))
    want, rows_all = L.moe_block(x, p, full)
    shared = L.ffn_block(x, p["shared"], full)
    total, rows = shared, 0
    for first in range(0, 8, 2):
        cut = {**p, "router": {"w": jnp.roll(p["router"]["w"], -first, 1)},
               **{k: {"w": p[k]["w"][first:first + 2]}
                  for k in ("wg", "wu", "wd")}}
        y, r = L.moe_block(x, cut, _moe_cfg(2))
        total, rows = total + (y - shared), rows + r
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(rows) == int(rows_all) == 2 * 5 * full.moe.top_k


def test_softmax_router_keeps_the_unrenormalized_top_k():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    probs = np.exp([2.0, 1.0, 0.0, -1.0]) / np.exp([2.0, 1.0, 0.0,
                                                    -1.0]).sum()
    mo = dataclasses.replace(get_config("deepseek-v2-lite").moe,
                             num_experts=4, top_k=2, held_count=None)
    gates, idx = L.route(logits, mo)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(np.asarray(gates), [probs[:2]], rtol=1e-6)
    assert float(gates.sum()) < 1.0                  # not renormalized


def test_topk_softmax_router_is_mixtrals():
    """Top-k of the raw logits, then a softmax over those k."""
    mo = get_config("mixtral-8x22b").moe
    assert mo.router == "topk_softmax" and mo.held == 8
    logits = jnp.array([[0.5, 3.0, -1.0, 2.0, 0.0, 0.0, 0.0, 0.0]])
    gates, idx = L.route(logits, mo)
    assert idx.tolist() == [[1, 3]]
    e = np.exp([3.0, 2.0])
    np.testing.assert_allclose(np.asarray(gates), [e / e.sum()], rtol=1e-6)


def test_dropless_computes_every_pick_of_one_expert():
    """Every token picks expert 0 first: all T picks are computed (a
    capacity bound would keep only part), and each token's output is
    its gates times its experts plus the shared experts. A capacity
    factor of 0.5 (5 rows an expert) drops some."""
    cfg = _moe_cfg(None)
    p = L.init_moe(KEY, cfg)
    router = p["router"]["w"].at[:, 0].set(0.0).at[0, 0].set(50.0)
    p = {**p, "router": {"w": router}}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.d_model))
    x = x.at[..., 0].set(3.0)                       # expert 0 wins
    y, rows = L.moe_block(x, p, cfg)
    K = cfg.moe.top_k
    assert int(rows) == 12 * K
    gates, idx = L.route(x[0] @ router, cfg.moe)
    assert bool(jnp.all(idx[:, 0] == 0))
    want = []
    for t in range(12):
        out = L.ffn_block(x[0, t], p["shared"], cfg)
        for k in range(K):
            e = int(idx[t, k])
            h = jax.nn.silu(x[0, t] @ p["wg"]["w"][e]) \
                * (x[0, t] @ p["wu"]["w"][e])
            out = out + gates[t, k] * (h @ p["wd"]["w"][e])
        want.append(out)
    np.testing.assert_allclose(np.asarray(y[0]), np.stack(want),
                               rtol=1e-4, atol=1e-5)
    _, capped = L.moe_block(x, p, _moe_cfg(None, capacity=0.5))
    assert int(capped) < 12 * K


def test_a_real_slot_does_not_see_the_other_slots():
    """One decode step, slot 0 active: its logits are the same whatever
    the inactive slots hold, and only its picks count."""
    cfg = get_config("deepseek-v2-lite-ep4").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    active = jnp.array([True, False, False, False])
    outs = []
    for seed in (1, 2):
        toks = jax.random.randint(jax.random.PRNGKey(seed), (4, 1), 0,
                                  cfg.vocab_size).at[0, 0].set(7)
        caches = T.init_caches(cfg, plan, 4, 8, jnp.float32)
        logits, _, routed = T.decode_step(
            params, toks, caches, jnp.array([0, 3, 5, 1]), cfg, plan,
            active=active, compute_dtype=jnp.float32, return_routed=True)
        outs.append((np.asarray(logits[0]), int(routed)))
    # row-wise GEMMs and a routed buffer whose other rows are zero: equal
    # up to float32 reassociation across buffer positions
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=0, atol=1e-6)
    moe_layers = cfg.num_layers - cfg.moe.first_dense
    assert outs[0][1] == outs[1][1] <= cfg.moe.top_k * moe_layers


def test_yarn_at_the_published_numbers():
    cfg = get_config("deepseek-v2-lite")
    rs, rd = cfg.rope_scaling, cfg.mla.qk_rope_dim
    assert L.yarn_ramp_bounds(rd, cfg.rope_theta, rs) == (10, 23)
    assert L.rope_softmax_scale(rs) == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert round(L.rope_softmax_scale(rs), 4) == 1.5896
    base = np.asarray(L.rope_frequencies(rd, cfg.rope_theta))
    yarn = np.asarray(L.rope_frequencies(rd, cfg.rope_theta, rs))
    np.testing.assert_array_equal(yarn[:11], base[:11])
    np.testing.assert_allclose(yarn[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((yarn[11:23] < base[11:23])
                  & (yarn[11:23] > base[11:23] / 40))
    # cos and sin are not rescaled: mscale equals mscale_all_dim
    x = jax.random.normal(KEY, (3, 2, rd))
    pos = jnp.arange(3)
    norms = jnp.linalg.norm(L.apply_rope(x, pos, cfg.rope_theta,
                                         scaling=rs), axis=-1)
    np.testing.assert_allclose(np.asarray(norms),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)


def test_counters_count_every_pick_when_all_experts_are_held():
    """With every expert held, each active token's top-k picks all land
    here: ``moe_routed_rows`` is top_k x MoE layers per token served, and
    the expert GEMMs ran slots x experts x MoE layers rows per tick."""
    cfg = get_config("deepseek-v2-lite").reduced()
    _, _, engine = _served(cfg, "float", "reference")
    stats = engine.runtime.stats
    moe_layers = cfg.num_layers - cfg.moe.first_dense
    tokens = engine.stats["tokens"]
    assert stats["moe_routed_rows"] == tokens * cfg.moe.top_k * moe_layers
    assert stats["moe_expert_rows"] == (engine.stats["ticks"] * 2
                                        * cfg.moe.num_experts * moe_layers)


def test_the_held_share_counts_only_its_picks():
    cfg = get_config("deepseek-v2-lite-ep4").reduced()
    _, _, engine = _served(cfg, "float", "reference")
    stats = engine.runtime.stats
    moe_layers = cfg.num_layers - cfg.moe.first_dense
    assert 0 < stats["moe_routed_rows"] < (
        engine.stats["tokens"] * cfg.moe.top_k * moe_layers)
    assert stats["moe_expert_rows"] == (engine.stats["ticks"] * 2
                                        * cfg.moe.held * moe_layers)


@pytest.mark.parametrize("scheme", ["int8_per_token", "int8_per_head"])
def test_mla_refuses_a_quantized_kv_cache(scheme):
    cfg = get_config("deepseek-v2-lite-ep4").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    with pytest.raises(ValueError, match="MLA.*kv_cache='float'"):
        ServeEngine(cfg, params, plan, batch_slots=2, max_len=16,
                    page_size=4, kv_cache=scheme)


def test_reduced_variants_keep_router_share_and_yarn():
    for name, held in (("deepseek-v2-lite", 8), ("deepseek-v2-lite-ep4", 2)):
        full, small = get_config(name), get_config(name).reduced()
        assert small.moe.router == "softmax_topk"
        assert small.moe.capacity_factor is None
        assert small.moe.held == held and small.moe.num_experts == 8
        assert small.rope_scaling == full.rope_scaling
        assert small.mla.q_lora_rank == 0
    ep4 = get_config("deepseek-v2-lite-ep4")
    assert ep4.num_layers == 9 and ep4.moe.held == 16
    assert ep4.moe.num_experts == 64
