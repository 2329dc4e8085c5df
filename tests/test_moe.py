"""MoE routing edge cases and the schema-v4 ``experts`` family.

Targets the corners the per-arch smokes gloss over: capacity overflow
(dropped tokens must not leak into outputs or calibration stats), top-k
tie stability (argsort routing must be deterministic under exactly tied
router logits), the exact-partition property of per-expert calibration
(the in-dispatch (E,) amax vector equals amax over precisely each
expert's kept tokens — mirroring the cluster-partition check in
test_adaptive.py), and expert-axis sharding of the per-expert scale
leaves under a 2-device mesh.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import MoEConfig, get_config
from repro.core.calibration import synthetic_calibration_batches
from repro.core.plan import LayerPlan, PrecisionPlan, QuantSpec
from repro.core.samp import SAMPEngine, moe_family_variant
from repro.distributed.sharding import Rules
from repro.kernels import ops
from repro.models import layers as L
from repro.models import transformer as T
from repro.quant import ptq

KEY = jax.random.PRNGKey(0)
EXPERT_SPEC = QuantSpec(weight="int8_per_channel", act="int8_per_tensor")


class FakeMesh:
    def __init__(self, shape_dict):
        self.shape = shape_dict
        self.axis_names = tuple(shape_dict)


def _dispatch(xt, logits, E, K, C):
    """Capacity dispatch of one token group under the top-k-then-softmax
    router."""
    gates, idx = L.route(logits, MoEConfig(num_experts=E, top_k=K,
                                           d_ff_expert=1))
    return L._dispatch_picks(xt, gates, idx, E, C)


# ---------------------------------------------------------------------------
# capacity overflow
# ---------------------------------------------------------------------------


def test_capacity_overflow_drops_tokens_gates_intact():
    """Force every token onto one expert with capacity C < T: exactly C
    assignments survive, dropped tokens contribute zero output, and the
    kept tokens' gates are STILL the softmax over their own top-k logits
    (capacity never renormalizes gates — Switch semantics)."""
    T_, D, E, K, C = 8, 4, 4, 2, 3
    xt = jax.random.normal(KEY, (T_, D))
    # expert 0 wins for every token; expert 1 is the runner-up
    logits = jnp.tile(jnp.array([[4.0, 2.0, -4.0, -4.0]]), (T_, 1))
    xe, st, sg, keep, slot = _dispatch(xt, logits, E, K, C)
    se = np.asarray(slot // C)
    keepn, stn, sgn = np.asarray(keep), np.asarray(st), np.asarray(sg)
    # the capacity bound applies per expert: C survive on each of the two
    # selected experts, everything else drops
    assert int((keepn & (se == 0)).sum()) == C
    assert int((keepn & (se == 1)).sum()) == C
    assert int(keepn.sum()) == 2 * C
    # gates: softmax over the token's own top-k logits, drop or no drop
    want = set(np.round(np.asarray(jax.nn.softmax(jnp.array([4.0, 2.0]))),
                        6).tolist())
    assert set(np.round(sgn[keepn], 6).tolist()) <= want
    # identity experts: each token's combined output is exactly the sum of
    # its SURVIVING gates times x — dropped assignments contribute zero
    y = np.asarray(L._combine_one(xe, st, sg, keep, slot, T_, D, xt.dtype))
    for t in range(T_):
        kept_gates = sgn[keepn & (stn == t)]
        np.testing.assert_allclose(y[t],
                                   kept_gates.sum() * np.asarray(xt[t]),
                                   rtol=1e-5, atol=1e-6)


def test_inactive_slots_take_no_capacity_in_a_mixtral_decode():
    """A capacity-bound Mixtral decode step at 64 slots, the last 4 live:
    each live row gets every one of its picks, whatever the 60 inactive
    slots hold, because an inactive slot's token routes nowhere. Without
    ``active``, inactive rows that copy the live tokens sort ahead of them
    into the same experts' C = 40 rows and drop the live rows' picks."""
    cfg = get_config("mixtral-8x22b").reduced()
    assert cfg.moe.capacity_factor is not None
    B, D = 64, cfg.d_model
    p = L.init_moe(KEY, cfg)
    live = jax.random.normal(jax.random.PRNGKey(1), (4, 1, D))
    active = jnp.arange(B) >= B - 4
    dropless = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                   capacity_factor=None))
    want, _ = L.moe_block(live, p, dropless)         # every pick computed
    copies = jnp.tile(live, (15, 1, 1))
    for junk in (jnp.zeros((B - 4, 1, D)), copies,
                 100.0 * jax.random.normal(jax.random.PRNGKey(2),
                                           (B - 4, 1, D))):
        x = jnp.concatenate([junk, live])
        y, rows = L.moe_block(x, p, cfg, active=active)
        np.testing.assert_allclose(np.asarray(y[B - 4:]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        assert int(rows) == 4 * cfg.moe.top_k
    y, rows = L.moe_block(jnp.concatenate([copies, live]), p, cfg)
    assert int(rows) < B * cfg.moe.top_k
    assert not np.allclose(np.asarray(y[B - 4:]), np.asarray(want),
                           rtol=1e-5, atol=1e-6)


def test_zero_padding_in_capacity_buffer():
    """Unfilled capacity slots are exact zeros — the invariant per-expert
    calibration relies on (amax over the buffer == amax over the kept
    tokens)."""
    T_, D, E, K, C = 4, 4, 4, 1, 8
    xt = jax.random.normal(KEY, (T_, D)) + 1.0
    logits = jnp.eye(E)[jnp.arange(T_) % E] * 3.0
    xe, st, sg, keep, slot = _dispatch(xt, logits, E, K, C)
    filled = np.zeros((E, C), bool)
    for s in np.asarray(slot[np.asarray(keep)]):
        filled[s // C, s % C] = True
    assert not bool(np.abs(np.asarray(xe)[~filled]).any())


# ---------------------------------------------------------------------------
# top-k tie stability
# ---------------------------------------------------------------------------


def test_top_k_tie_stability():
    """Exactly tied router logits route deterministically (lowest expert
    index wins in lax.top_k) and identically across eager/jit — the
    property the bit-exact fused-vs-reference parity rests on."""
    T_, D, E, K, C = 6, 4, 4, 2, 4
    xt = jax.random.normal(KEY, (T_, D))
    logits = jnp.zeros((T_, E))                   # all-way tie
    out_eager = _dispatch(xt, logits, E, K, C)
    out_jit = jax.jit(_dispatch, static_argnums=(2, 3, 4))(
        xt, logits, E, K, C)
    for a, b in zip(out_eager, out_jit):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, st, _, keep, slot = out_eager
    se = np.asarray(slot // C)
    # lowest-index tie-break: every token lands on experts {0, 1}
    assert set(se[np.asarray(keep)].tolist()) <= {0, 1}
    # and the assignment is reproducible call-to-call
    again = _dispatch(xt, logits, E, K, C)
    np.testing.assert_array_equal(np.asarray(slot), np.asarray(again[4]))


# ---------------------------------------------------------------------------
# per-expert calibration: exact partition
# ---------------------------------------------------------------------------


def test_per_expert_amax_is_exact_partition():
    """The in-dispatch per-expert amax vector equals amax computed over
    precisely the tokens each expert kept — routing partitions the
    calibration exactly (zero tolerance), mirroring the cluster-partition
    check in test_adaptive.py."""
    T_, D, E, K, C = 16, 8, 4, 2, 5
    xt = jax.random.normal(KEY, (T_, D))
    logits = jax.random.normal(jax.random.PRNGKey(1), (T_, E))
    xe, st, sg, keep, slot = _dispatch(xt, logits, E, K, C)
    obs = {}
    L.observe_per_expert(obs, "expert_in", xe)
    got = np.asarray(obs["expert_in"])
    assert got.shape == (E,)
    se = np.asarray(slot // C)
    stn, keepn = np.asarray(st), np.asarray(keep)
    want = np.zeros(E, np.float32)
    for e in range(E):
        toks = stn[keepn & (se == e)]
        if len(toks):
            want[e] = np.abs(np.asarray(xt)[toks]).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


def test_capture_stats_emits_expert_vectors():
    """End-to-end: calibrating a reduced mixtral under an experts-family
    plan records (E,)-length expert_in/expert_hidden lists per layer, and
    apply_plan turns them into (steps, E, 1, 1) static scale leaves."""
    cfg = get_config("mixtral-8x22b").reduced()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)
    batches = synthetic_calibration_batches(cfg, num_batches=1, seq_len=16)
    plan = PrecisionPlan.uniform(
        cfg.num_layers, LayerPlan(experts=EXPERT_SPEC),
        float_dtype="float32")
    stats = eng.calibrate(params, batches, precision=plan)
    E = cfg.moe.num_experts
    for i in range(cfg.num_layers):
        for site in ("expert_in", "expert_hidden"):
            v = stats[f"layer{i}"][site]
            assert isinstance(v, list) and len(v) == E
            assert all(x > 0 for x in v)
    qparams, _ = eng.apply(params, stats, plan)
    xs = [v for p, v in jax.tree_util.tree_leaves_with_path(qparams)
          if jax.tree_util.keystr(p).endswith("['xs']")
          and getattr(v, "ndim", 0) == 4]
    assert xs and all(v.shape[-3:] == (E, 1, 1) for v in xs)


def test_missing_expert_stats_is_actionable():
    """A static-acts experts family without calibrated expert sites must
    name the missing site and the fix."""
    cfg = get_config("mixtral-8x22b").reduced()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)
    plan = PrecisionPlan.uniform(
        cfg.num_layers, LayerPlan(experts=EXPERT_SPEC),
        float_dtype="float32")
    # scalar-only stats: what a pre-v4 calibration run would have produced
    stats = {f"layer{i}": {"ffn_in": 1.0, "ffn_hidden": 1.0}
             for i in range(cfg.num_layers)}
    with pytest.raises(ValueError, match="expert_in.*capture_stats"):
        eng.apply(params, stats, plan)


# ---------------------------------------------------------------------------
# fused kernel unit parity + expert-axis sharding
# ---------------------------------------------------------------------------


def test_quant_expert_gemm_matches_reference_einsum():
    """Unit parity of the batched per-expert kernel against the reference
    dequantized einsum, static and dynamic activation scales."""
    G, E, C, D, F = 2, 4, 8, 16, 12
    k1, k2 = jax.random.split(KEY)
    xe = jax.random.normal(k1, (G, E, C, D))
    w = jax.random.normal(k2, (E, D, F))
    wq = ptq.quantize_weight(w, "int8_per_channel")
    ref = jnp.einsum("gecd,edf->gecf", xe, w)
    xs = jnp.full((E, 1, 1), float(jnp.abs(xe).max()) / 127.0)
    for scales in (xs, None):
        got = ops.quant_expert_gemm(xe, wq.values, wq.scale, scales)
        assert got.shape == ref.shape
        err = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
        assert err < 0.1          # int8 quantization error bound


@pytest.mark.parametrize("lead", [(), (1,), (2,)])
@pytest.mark.parametrize("scales", ["per_expert", "scalar", "dynamic"])
def test_grouped_expert_kernel_matches_the_per_expert_einsum(lead, scales):
    """The grouped ``quant_expert_gemm`` kernel (one call, its grid over the
    experts) against the reference path's per-expert int8 einsum on the
    same int8 codes: int32 accumulation is exact, so only the float
    epilogue's rounding may differ."""
    E, C, D, F = 3, 8, 48, 40
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    xe = jax.random.normal(k1, lead + (E, C, D))
    wq = ptq.quantize_weight(jax.random.normal(k2, (E, D, F)),
                             "int8_per_channel")
    amax = jnp.abs(xe).max(axis=tuple(i for i in range(xe.ndim)
                                      if i != xe.ndim - 3))
    xs = {"per_expert": (amax / 127.0).reshape(E, 1, 1),
          "scalar": jnp.abs(xe).max() / 127.0, "dynamic": None}[scales]
    got = ops.quant_expert_gemm(xe, wq.values, wq.scale, xs)
    want = L._expert_gemm(xe, wq, xs, None, "ffn_in_e")
    assert got.shape == want.shape == lead + (E, C, F)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6 * float(
                                   jnp.abs(want).max()))


def test_expert_scale_leaves_shard_on_expert_axis():
    """Per-expert int8 values AND their (steps, E, 1, F) scale leaves ride
    the expert axis under a 2-device mesh; per-expert xs shards the same
    way; the router stays replicated."""
    cfg = get_config("mixtral-8x22b").reduced()    # E=4, divisible by 2
    rules = Rules(cfg, FakeMesh({"data": 2, "model": 1}))
    E = cfg.moe.num_experts
    w = rules.spec_for("groups/0/layers/0/ffn/wg/w/values",
                       (cfg.num_layers, E, cfg.d_model, 32))
    assert w[1] == "data"
    s = rules.spec_for("groups/0/layers/0/ffn/wg/w/scale",
                       (cfg.num_layers, E, 1, 32))
    assert s[1] == "data" and s[2] is None
    xs = rules.spec_for("groups/0/layers/0/ffn/wg/xs",
                        (cfg.num_layers, E, 1, 1))
    assert xs == P(None, "data", None, None)
    router = rules.spec_for("groups/0/layers/0/ffn/router/w",
                            (cfg.num_layers, cfg.d_model, E))
    assert router == P(*(None,) * 3)


def test_indivisible_expert_count_stays_unsharded():
    """E not divisible by the data axis -> per-expert xs replicates (the
    same divisibility discipline as the weight rule)."""
    cfg = get_config("mixtral-8x22b").reduced()
    rules = Rules(cfg, FakeMesh({"data": 3, "model": 1}))
    xs = rules.spec_for("groups/0/layers/0/ffn/wg/xs",
                        (cfg.num_layers, cfg.moe.num_experts, 1, 1))
    assert xs == P(None, None, None, None)
