"""PTQ + SAMP engine end-to-end on reduced models."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import LayerMode, PrecisionPlan
from repro.core.quantize import QuantizedTensor
from repro.core.samp import SAMPEngine
from repro.models import transformer as T
from repro.quant import ptq

KEY = jax.random.PRNGKey(0)


def setup(arch, head=None):
    cfg = get_config(arch).reduced()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision, head=head)
    batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (2, 16),
                                             0, cfg.vocab_size)}
               for i in range(3)]
    return cfg, eng, params, batches


def test_capture_stats_covers_all_layers():
    cfg, eng, params, batches = setup("qwen2-0.5b")
    stats = eng.calibrate(params, batches)
    assert len(stats) == cfg.num_layers
    for lk, sites in stats.items():
        assert {"attn_in", "attn_out", "ffn_in", "ffn_hidden",
                "q", "k", "p", "v"} <= set(sites)
        # k_cache/v_cache are per-head vectors (lists); the rest scalar
        assert all(min(v) > 0 if isinstance(v, list) else v > 0
                   for v in sites.values())


def test_minmax_monotone_in_batches():
    cfg, eng, params, batches = setup("qwen2-0.5b")
    s1 = eng.calibrate(params, batches[:1])
    s3 = eng.calibrate(params, batches)
    for lk in s1:
        for site in s1[lk]:
            a, b = s1[lk][site], s3[lk][site]
            if isinstance(a, list):
                assert all(y >= x - 1e-9 for x, y in zip(a, b))
            else:
                assert b >= a - 1e-9


@pytest.mark.parametrize("mode", [LayerMode.QUANT_FFN_ONLY,
                                  LayerMode.FULLY_QUANT])
def test_apply_policy_quantizes_right_weights(mode):
    cfg, eng, params, batches = setup("qwen2-0.5b")
    stats = eng.calibrate(params, batches)
    k = cfg.num_layers // 2
    policy = PrecisionPlan.prefix(cfg.num_layers, k, mode, "float32")
    qp, plan = eng.apply(params, stats, policy)
    layers = T.unpack_layers(qp, plan)
    for i, lp in enumerate(layers):
        ffn_q = isinstance(lp["ffn"]["wg"]["w"], QuantizedTensor)
        mha_q = isinstance(lp["attn"]["wq"]["w"], QuantizedTensor)
        if i < k:
            assert ffn_q
            assert mha_q == (mode is LayerMode.FULLY_QUANT)
            if mode is LayerMode.FULLY_QUANT:
                assert "p_scale" in lp["attn"]
        else:
            assert not ffn_q and not mha_q


def test_quantized_outputs_close_to_float():
    cfg, eng, params, batches = setup("qwen2-0.5b")
    stats = eng.calibrate(params, batches)
    ref, _ = T.forward(params, batches[0], cfg, eng.float_plan,
                       compute_dtype=jnp.float32)
    errs = {}
    for mode in (LayerMode.QUANT_FFN_ONLY, LayerMode.FULLY_QUANT):
        policy = PrecisionPlan.prefix(cfg.num_layers, cfg.num_layers, mode,
                                      "float32")
        qp, plan = eng.apply(params, stats, policy)
        out, _ = T.forward(qp, batches[0], cfg, plan,
                           compute_dtype=jnp.float32)
        rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        errs[mode] = rel
        assert np.isfinite(rel) and rel < 0.5
    # the paper's §4.2 finding: quantizing MHA (softmax path) hurts more
    assert errs[LayerMode.FULLY_QUANT] >= errs[LayerMode.QUANT_FFN_ONLY] - 1e-3


def test_unsigned_softmax_fix_reduces_error():
    """Beyond-paper: unsigned softmax quantization beats symmetric."""
    cfg = get_config("qwen2-0.5b").reduced()
    errs = {}
    for sm in ("symmetric", "unsigned"):
        scheme = T.QuantScheme(softmax_mode=sm)
        eng = SAMPEngine(cfg, scheme=scheme, float_dtype="float32")
        params = T.init_params(KEY, cfg, eng.float_precision)
        batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i),
                                                 (2, 16), 0, cfg.vocab_size)}
                   for i in range(3)]
        stats = eng.calibrate(params, batches)
        ref, _ = T.forward(params, batches[0], cfg, eng.float_plan,
                           compute_dtype=jnp.float32)
        policy = PrecisionPlan.prefix(cfg.num_layers, cfg.num_layers,
                                      LayerMode.FULLY_QUANT, "float32")
        qp, plan = eng.apply(params, stats, policy)
        out, _ = T.forward(qp, batches[0], cfg, plan, scheme,
                           compute_dtype=jnp.float32)
        errs[sm] = float(jnp.mean(jnp.abs(out - ref)))
    assert errs["unsigned"] < errs["symmetric"]


def test_dynamic_acts_need_no_stats():
    cfg = get_config("qwen2-0.5b").reduced()
    scheme = T.QuantScheme(dynamic_acts=True)
    eng = SAMPEngine(cfg, scheme=scheme, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)
    policy = PrecisionPlan.prefix(cfg.num_layers, cfg.num_layers,
                                  LayerMode.QUANT_FFN_ONLY, "float32",
                                  dynamic_acts=True)
    qp, plan = ptq.apply_plan(params, cfg, policy, {}, scheme=scheme,
                              float_plan=eng.float_plan)
    batch = {"tokens": jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)}
    out, _ = T.forward(qp, batch, cfg, plan, scheme,
                       compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(out)))
    layers = T.unpack_layers(qp, plan)
    assert "xs" not in layers[0]["ffn"]["wg"]      # no static scales stored


def test_expert_weight_quantization_shape():
    cfg = get_config("mixtral-8x22b").reduced()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)
    batches = [{"tokens": jax.random.randint(KEY, (2, 16), 0,
                                             cfg.vocab_size)}]
    stats = eng.calibrate(params, batches)
    policy = PrecisionPlan.prefix(cfg.num_layers, cfg.num_layers,
                                  LayerMode.QUANT_FFN_ONLY, "float32")
    qp, plan = eng.apply(params, stats, policy)
    layers = T.unpack_layers(qp, plan)
    wg = layers[0]["ffn"]["wg"]["w"]
    assert isinstance(wg, QuantizedTensor)
    E, D, F = wg.values.shape
    assert wg.scale.shape == (E, 1, F)             # per-expert per-channel
    # router must stay float
    assert not isinstance(layers[0]["ffn"]["router"]["w"], QuantizedTensor)


def test_sweep_and_recommend():
    cfg, eng, params, batches = setup("qwen2-0.5b")
    stats = eng.calibrate(params, batches)
    ref, _ = T.forward(params, batches[0], cfg, eng.float_plan,
                       compute_dtype=jnp.float32)

    def eval_fn(qp, plan, policy):
        out, _ = T.forward(qp, batches[0], cfg, plan,
                           compute_dtype=jnp.float32)
        return 1.0 - float(jnp.mean(jnp.abs(out - ref))
                           / (jnp.mean(jnp.abs(ref)) + 1e-9))

    def latency_fn(qp, plan, policy):
        # simple proxy: fewer float layers -> lower latency
        return 1.0 - 0.02 * policy.num_quant_ffn - 0.01 * policy.num_quant_mha

    pts = eng.sweep(params, stats, eval_fn, latency_fn, stride=2)
    assert pts[0].mode_name == "float"
    results = eng.recommend(pts)
    assert {r.mode_name for r in results} == {"fully_quant",
                                              "quant_ffn_only"}
    for r in results:
        assert r.point.latency <= pts[0].latency
    top = eng.top5(pts)
    assert 0 < len(top) <= 5
