"""Whole-layer int8 dataflow battery (schema-v3 ``softmax``/``norm``).

The acceptance suite for the fully-int8 layer span:

* unsigned-softmax round-trip error bounds (property tests): dequantized
  probability rows still sum to ~1 and every element stays within half a
  code step of the exact softmax;
* fused-vs-reference forward parity for every (softmax, norm) scheme
  combination the golden plan can host;
* the whole-layer span: under a uniform fully-quantized plan with
  ``softmax='uint8'`` + ``norm='int8'``, backend-level spies prove the
  attn -> attn_out -> residual/norm -> ffn_in -> ffn_out chain hands
  ``QuantActivation`` (int8) between every GEMM — no float tensor
  materializes between qkv and ffn_out;
* the two-pass uint8-softmax decode kernel against a numpy QDQ oracle;
* schema-v3 plan round-trip (fingerprints of v1 plans stay byte-stable)
  and plan_lint rejection of malformed v3 fields;
* the ``benchmarks/softmax_range.py`` machine-readable JSON section,
  consumed here as the calibration fixture justifying the uint8 scheme.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import hypothesis, st

from repro.configs import get_config
from repro.core.calibration import synthetic_calibration_batches
from repro.core.plan import LayerMode, LayerPlan, PrecisionPlan
from repro.core.quantize import UINT8_MAX, quantize_unsigned
from repro.core.samp import int8_dataflow_variant
from repro.kernels import ops
from repro.kernels.backend import FusedBackend, QuantActivation, get_backend
from repro.models import transformer as T
from repro.quant import ptq
from repro.toolkit.plan_lint import lint

KEY = jax.random.PRNGKey(0)
GOLDEN = "tests/data/golden_plan.json"


def rel_linf(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / (np.abs(a).max() + 1e-9))


def with_flow(plan: PrecisionPlan, softmax: bool, norm: bool):
    """Apply the dataflow schemes to every eligible layer of ``plan``."""
    layers = []
    for lp in plan.layers:
        sm = "uint8" if (softmax and lp.qkv.quantized) else None
        nm = "int8" if (norm and all(
            lp.spec(b).quantized and lp.spec(b).static_acts
            for b in ("attn_out", "ffn_in"))) else None
        layers.append(lp.with_dataflow(softmax=sm, norm=nm))
    return dataclasses.replace(plan, layers=tuple(layers))


SPAN_LAYER = LayerPlan.for_mode(LayerMode.FULLY_QUANT, softmax="uint8",
                                norm="int8")


@pytest.fixture(scope="module")
def flow_setup():
    """Float bert-base reduced + stats captured under the golden plan's
    full-dataflow variant (superset of every combo's observer sites)."""
    cfg = get_config("bert-base").reduced()
    golden = PrecisionPlan.load(GOLDEN)
    assert golden.num_layers == cfg.num_layers
    float_plan = T.build_plan(
        cfg, PrecisionPlan.full_float(cfg.num_layers, "float32"))
    params = T.init_params(KEY, cfg, PrecisionPlan.full_float(
        cfg.num_layers, "float32"))
    batches = synthetic_calibration_batches(cfg, num_batches=2, seq_len=16)
    stats = ptq.capture_stats(params, batches, cfg, float_plan,
                              precision=with_flow(golden, True, True))
    return cfg, params, float_plan, stats, batches[0]


@pytest.fixture(scope="module")
def span_setup():
    """Uniform fully-quantized whole-layer-span plan + its stats."""
    cfg = get_config("bert-base").reduced()
    plan = PrecisionPlan.uniform(cfg.num_layers, SPAN_LAYER,
                                 float_dtype="float32")
    float_plan = T.build_plan(
        cfg, PrecisionPlan.full_float(cfg.num_layers, "float32"))
    params = T.init_params(KEY, cfg, PrecisionPlan.full_float(
        cfg.num_layers, "float32"))
    batches = synthetic_calibration_batches(cfg, num_batches=2, seq_len=16)
    stats = ptq.capture_stats(params, batches, cfg, float_plan,
                              precision=plan)
    qparams, qplan = ptq.apply_plan(params, cfg, plan, stats,
                                    float_plan=float_plan)
    return cfg, qparams, qplan, batches[0]


# ---------------------------------------------------------------------------
# unsigned-softmax round-trip bounds (property tests)
# ---------------------------------------------------------------------------


@hypothesis.given(st.integers(0, 2**31 - 1), st.integers(2, 96),
                  st.floats(0.25, 8.0))
@hypothesis.settings(max_examples=30, deadline=None)
def test_unsigned_softmax_roundtrip_bounds(seed, n, temp):
    """Dequantized uint8-scheme probabilities stay within half a code step
    per element, and rows still sum to ~1 (within n/2 code steps)."""
    rng = np.random.default_rng(seed)
    logits = rng.standard_normal((4, n)).astype(np.float32) * temp
    p = np.exp(logits - logits.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    amax = float(p.max())                      # calibrated amax covers p
    qt = quantize_unsigned(jnp.asarray(p), amax)
    scale = float(np.asarray(qt.scale))
    assert scale * UINT8_MAX >= amax - 1e-6    # no clipping below amax
    deq = np.asarray(qt.dequantize(jnp.float32))
    assert deq.min() >= 0.0                    # zero point pins code 0 at 0
    assert np.abs(deq - p).max() <= scale / 2 + 1e-6
    assert np.abs(deq.sum(axis=-1) - 1.0).max() <= n * scale / 2 + 1e-5


@hypothesis.given(st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=20, deadline=None)
def test_unsigned_codes_cover_full_range(seed):
    """The scheme's point: a [0, amax] tensor maps onto all 256 codes —
    code -128 is exactly 0.0 and code 127 is exactly amax."""
    rng = np.random.default_rng(seed)
    amax = float(rng.uniform(0.1, 1.0))
    x = jnp.asarray(np.linspace(0.0, amax, 1024, dtype=np.float32))
    qt = quantize_unsigned(x, amax)
    codes = np.asarray(qt.values, np.int32)
    assert codes.min() == -128 and codes.max() == 127
    assert len(np.unique(codes)) == 256


# ---------------------------------------------------------------------------
# fused-vs-reference parity, every scheme combination
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("softmax,norm", [
    (False, False), (True, False), (False, True), (True, True)])
def test_golden_plan_scheme_combo_parity(flow_setup, softmax, norm):
    """Every (softmax, norm) combination on the golden plan's eligible
    layers: fused (interpret-mode Pallas) matches reference."""
    cfg, params, float_plan, stats, batch = flow_setup
    plan = with_flow(PrecisionPlan.load(GOLDEN), softmax, norm)
    if softmax or norm:                        # the combo actually engages
        assert any(lp.softmax != "float" or lp.norm != "float"
                   for lp in plan.layers)
    qparams, qplan = ptq.apply_plan(params, cfg, plan, stats,
                                    float_plan=float_plan)
    ref_out, _ = T.forward(qparams, batch, cfg, qplan,
                           compute_dtype=jnp.float32)
    fused_out, _ = T.forward(qparams, batch, cfg, qplan,
                             compute_dtype=jnp.float32,
                             backend=get_backend("fused"))
    assert rel_linf(ref_out, fused_out) < 5e-3


def test_uint8_softmax_changes_the_numbers(flow_setup):
    """The uint8 scheme is a real QDQ, not a no-op: outputs differ from
    the float-softmax plan on both backends, by a small bounded amount."""
    cfg, params, float_plan, stats, batch = flow_setup
    base = PrecisionPlan.load(GOLDEN)
    flow = with_flow(base, True, False)
    qp0, qplan0 = ptq.apply_plan(params, cfg, base, stats,
                                 float_plan=float_plan)
    qp1, qplan1 = ptq.apply_plan(params, cfg, flow, stats,
                                 float_plan=float_plan)
    a, _ = T.forward(qp0, batch, cfg, qplan0, compute_dtype=jnp.float32)
    b, _ = T.forward(qp1, batch, cfg, qplan1, compute_dtype=jnp.float32)
    d = rel_linf(a, b)
    assert 0.0 < d < 5e-2, d


# ---------------------------------------------------------------------------
# the whole-layer int8 span
# ---------------------------------------------------------------------------


def test_whole_layer_span_no_float_boundaries(span_setup, monkeypatch):
    """Backend-level spies prove the span: attention emits int8, attn_out /
    ffn GEMMs consume and emit int8, the residual boundary consumes int8 —
    zero float materialization between the layer's four GEMMs."""
    cfg, qparams, qplan, batch = span_setup
    linear_inputs = []                         # True = QuantActivation in
    linear_outputs = []
    attn_claims = []
    addnorm_deltas = []
    orig_linear = FusedBackend.linear
    orig_attn = FusedBackend.attention
    orig_addnorm = FusedBackend.addnorm

    def linear(self, x, p, *, act=None):
        out = orig_linear(self, x, p, act=act)
        linear_inputs.append(isinstance(x, QuantActivation))
        linear_outputs.append(isinstance(out, QuantActivation))
        return out

    def attention(self, *a, **kw):
        out = orig_attn(self, *a, **kw)
        attn_claims.append(isinstance(out, QuantActivation))
        return out

    def addnorm(self, delta, *a, **kw):
        addnorm_deltas.append(isinstance(delta, QuantActivation))
        return orig_addnorm(self, delta, *a, **kw)

    monkeypatch.setattr(FusedBackend, "linear", linear)
    monkeypatch.setattr(FusedBackend, "attention", attention)
    monkeypatch.setattr(FusedBackend, "addnorm", addnorm)
    kernels = {"quant_flash_attention": [], "quant_linear": [],
               "addnorm_quant": []}
    _orig_flash = ops.quant_flash_attention
    _orig_qlin = ops.quant_linear
    _orig_addnq = ops.addnorm_quant

    def flash(*a, **kw):
        kernels["quant_flash_attention"].append(kw.get("o_scale") is not None)
        return _orig_flash(*a, **kw)

    def qlin(x_q, *a, **kw):
        kernels["quant_linear"].append(
            (x_q.dtype == jnp.int8, kw.get("out_scale") is not None))
        return _orig_qlin(x_q, *a, **kw)

    def addnq(x, *a, **kw):
        kernels["addnorm_quant"].append(
            (x.dtype == jnp.int8, kw.get("x_in_scale") is not None))
        return _orig_addnq(x, *a, **kw)

    monkeypatch.setattr(ops, "quant_flash_attention", flash)
    monkeypatch.setattr(ops, "quant_linear", qlin)
    monkeypatch.setattr(ops, "addnorm_quant", addnq)

    ref_out, _ = T.forward(qparams, batch, cfg, qplan,
                           compute_dtype=jnp.float32)
    fused_out, _ = T.forward(qparams, batch, cfg, qplan,
                             compute_dtype=jnp.float32,
                             backend=get_backend("fused"))
    assert rel_linf(ref_out, fused_out) < 5e-3

    # the fused attention claimed the op and emitted int8 (one scan trace)
    assert attn_claims and all(attn_claims), attn_claims
    assert kernels["quant_flash_attention"] == [True]
    # 6 GEMMs per layer trace: wq/wk/wv take the float residual stream,
    # wo/wi/ffn_out take pre-quantized int8 hand-offs
    assert linear_inputs == [False] * 3 + [True] * 3, linear_inputs
    # wo and wi requantize in-epilogue (out_xs); ffn_out emits the float
    # delta for the residual stream; qkv emits float into the attention
    assert linear_outputs == [False] * 3 + [True, True, False]
    assert [o for _, o in kernels["quant_linear"]] \
        == [False] * 3 + [True, True, False]
    assert all(q for q, _ in kernels["quant_linear"])  # int8 into the MXU
    # the residual boundary consumed the int8 delta directly
    assert addnorm_deltas == [True]
    assert kernels["addnorm_quant"] == [(True, True)]


def test_span_plan_groups_are_scheme_homogeneous(span_setup):
    """build_plan threads the softmax scheme into the execution groups."""
    cfg = span_setup[0]
    plan = PrecisionPlan.uniform(cfg.num_layers, SPAN_LAYER,
                                 float_dtype="float32")
    groups = T.build_plan(cfg, plan)
    assert all(g.softmax == "uint8" for g in groups)
    float_groups = T.build_plan(
        cfg, PrecisionPlan.full_float(cfg.num_layers, "float32"))
    assert all(g.softmax is None for g in float_groups)


def test_int8_dataflow_variant_marks_eligible_layers():
    """The autotune search-space helper: golden layers 0/3 (static fully-
    quant) gain both schemes, layer 1 (dynamic ffn, float qkv) and layer 2
    (float) stay; a full-float plan has no variant."""
    golden = PrecisionPlan.load(GOLDEN)
    variant = int8_dataflow_variant(golden)
    assert variant is not None
    assert [lp.softmax for lp in variant.layers] \
        == ["uint8", "float", "float", "uint8"]
    assert [lp.norm for lp in variant.layers] \
        == ["int8", "float", "float", "int8"]
    # GEMM blocks untouched: stripping the schemes recovers the original
    stripped = dataclasses.replace(variant, layers=tuple(
        dataclasses.replace(lp, softmax="float", norm="float")
        for lp in variant.layers))
    assert stripped.fingerprint() == golden.fingerprint()
    assert int8_dataflow_variant(
        PrecisionPlan.full_float(4, "float32")) is None


# ---------------------------------------------------------------------------
# two-pass uint8-softmax decode kernel vs a numpy QDQ oracle
# ---------------------------------------------------------------------------


def _decode_oracle(q, k_pages, v_pages, page_table, lengths, ks, vs,
                   scale, p_scale):
    """Per-head-scale paged decode with the uint8 softmax QDQ applied to
    the *final* probabilities (the kernel's two-pass contract)."""
    B, Hkv, g, hd = q.shape
    _, ps, _, _ = k_pages.shape
    out = np.zeros((B, Hkv, g, hd), np.float32)
    for b in range(B):
        if lengths[b] <= 0:
            continue
        kk, vv = [], []
        for j, pg in enumerate(page_table[b]):
            if pg < 0:
                continue
            for t in range(ps):
                if j * ps + t >= lengths[b]:
                    continue
                kk.append(k_pages[pg, t].astype(np.float32) * ks[None, :].T)
                vv.append(v_pages[pg, t].astype(np.float32) * vs[None, :].T)
        k = np.stack(kk)                       # (L, Hkv, hd)
        v = np.stack(vv)
        for h in range(Hkv):
            s = (q[b, h].astype(np.float32) * scale) @ k[:, h].T
            p = np.exp(s - s.max(axis=1, keepdims=True))
            p /= p.sum(axis=1, keepdims=True)
            if p_scale is not None:
                codes = np.clip(np.round(p / p_scale), 0, 255)
                p = codes * p_scale            # uint8 QDQ on the final p
            out[b, h] = p @ v[:, h]
    return out


@pytest.mark.parametrize("p_scale", [None, 1.0 / 255])
def test_decode_two_pass_uint8_softmax(p_scale):
    rng = np.random.default_rng(7)
    B, Hkv, g, hd, ps, pps = 3, 2, 2, 8, 4, 3
    q = rng.standard_normal((B, Hkv, g, hd)).astype(np.float32)
    k = rng.integers(-127, 128, (B * pps, ps, Hkv, hd)).astype(np.int8)
    v = rng.integers(-127, 128, (B * pps, ps, Hkv, hd)).astype(np.int8)
    ks = rng.uniform(0.01, 0.05, (Hkv,)).astype(np.float32)
    vs = rng.uniform(0.01, 0.05, (Hkv,)).astype(np.float32)
    lengths = np.array([5, ps * pps, 1], np.int32)
    pt = -np.ones((B, pps), np.int32)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // ps)):
            pt[b, j] = b * pps + j
    scale = 1.0 / np.sqrt(hd)
    # the kernel takes head-major (NP, Hkv, ps, hd) pages
    got = ops.decode_attention(
        jnp.asarray(q), jnp.asarray(k.swapaxes(1, 2)),
        jnp.asarray(v.swapaxes(1, 2)), jnp.asarray(pt),
        jnp.asarray(lengths), k_scale=jnp.asarray(ks),
        v_scale=jnp.asarray(vs), per_head=True, scale=float(scale),
        p_scale=p_scale)
    want = _decode_oracle(q, k, v, pt, lengths, ks, vs, scale, p_scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


# ---------------------------------------------------------------------------
# schema v3: round-trip, fingerprints, lint rejection
# ---------------------------------------------------------------------------


def test_schema_v3_roundtrip_and_minimal_version():
    span = PrecisionPlan.uniform(4, SPAN_LAYER, float_dtype="float32")
    d = span.to_dict()
    assert d["schema_version"] == 3
    assert d["layers"][0]["softmax"] == "uint8"
    assert d["layers"][0]["norm"] == "int8"
    reloaded = PrecisionPlan.from_json(span.to_json())
    assert reloaded == span
    assert reloaded.fingerprint() == span.fingerprint()
    # v1 plans stay v1 — and byte-stable — after the v3 fields landed
    golden = PrecisionPlan.load(GOLDEN)
    assert golden.to_dict()["schema_version"] == 1
    assert "softmax" not in json.dumps(golden.to_dict())
    assert PrecisionPlan.from_json(golden.to_json()) == golden


def test_schema_v3_lint_accepts_valid_plan(tmp_path):
    span = PrecisionPlan.uniform(4, SPAN_LAYER, float_dtype="float32")
    path = tmp_path / "span.json"
    path.write_text(span.to_json())
    plan = lint(str(path), num_layers=4, log=lambda *_: None)
    assert plan.softmax_schemes == ("uint8",) * 4
    assert plan.norm_schemes == ("int8",) * 4


def test_schema_v3_lint_rejections(tmp_path):
    golden = json.load(open(GOLDEN))

    def write(d, name):
        p = tmp_path / name
        p.write_text(json.dumps(d))
        return str(p)

    # v3 fields under a v1/v2 schema_version header are rejected
    d = json.loads(json.dumps(golden))
    d["layers"][0]["softmax"] = "uint8"
    with pytest.raises(ValueError, match="schema v3"):
        lint(write(d, "v1_softmax.json"), log=lambda *_: None)
    # unknown scheme values are rejected
    d = json.loads(json.dumps(golden))
    d["schema_version"] = 3
    d["layers"][0]["softmax"] = "int4"
    with pytest.raises(ValueError, match="softmax scheme"):
        lint(write(d, "bad_scheme.json"), log=lambda *_: None)
    # softmax='uint8' on a float-attention layer is rejected
    d = json.loads(json.dumps(golden))
    d["schema_version"] = 3
    d["layers"][2]["softmax"] = "uint8"
    with pytest.raises(ValueError, match="uint8"):
        lint(write(d, "float_uint8.json"), log=lambda *_: None)
    # norm='int8' over a dynamic-act ffn_in is rejected
    d = json.loads(json.dumps(golden))
    d["schema_version"] = 3
    d["layers"][1]["norm"] = "int8"
    with pytest.raises(ValueError, match="norm='int8'"):
        lint(write(d, "dyn_norm.json"), log=lambda *_: None)


def test_layerplan_dataflow_validation_direct():
    with pytest.raises(ValueError, match="uint8"):
        LayerPlan(softmax="uint8")             # float layer can't consume
    with pytest.raises(ValueError, match="norm='int8'"):
        LayerPlan.for_mode(LayerMode.FULLY_QUANT, dynamic_acts=True,
                           norm="int8")
    lp = LayerPlan.for_mode(LayerMode.FULLY_QUANT, softmax="uint8",
                            norm="int8")
    assert lp.with_dataflow(softmax="float", norm="float").softmax == "float"
    # kv-only decode layers may take the uint8 softmax without qkv
    LayerPlan(kv_cache="int8_per_head", softmax="uint8")


# ---------------------------------------------------------------------------
# softmax_range JSON section as a calibration fixture
# ---------------------------------------------------------------------------


def test_softmax_range_json_fixture():
    """The benchmark's machine-readable section: parses, is internally
    consistent, and shows the unsigned scheme strictly dominating the
    symmetric one on softmax outputs — the premise of ``softmax='uint8'``."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import softmax_range
    lines = []
    r = softmax_range.collect(n_batches=1, batch=4, seq=16, layers=2,
                              emit=lines.append)
    text = "\n".join(lines)
    start = text.index("```json") + len("```json")
    end = text.index("```", start)
    report = json.loads(text[start:end])
    assert report == r["report"]
    schemes = report["softmax_range"]["schemes"]
    for s in schemes.values():
        assert s["codes_used"] + s["codes_unused"] == 256
        assert s["utilization"] == pytest.approx(s["codes_used"] / 256)
    assert schemes["softmax_unsigned"]["codes_used"] \
        > schemes["softmax_symmetric"]["codes_used"]
