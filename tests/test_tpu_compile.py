"""Ahead-of-time TPU compiles of the main-path Pallas kernels.

Each test lowers one kernel at published widths (bert-base, qwen2-0.5b)
for a described TPU v5e chip and compiles it with the TPU compiler, which
is installed even where no chip is attached. Mosaic rejects block layouts,
slices and VMEM budgets that interpret mode accepts, so these compiles are
the CPU-side guard that the kernels still build for the chip. Nothing runs:
the tests check that the compiled executable holds the kernel
(``tpu_custom_call``), not what it computes.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers each
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import addnorm_quant as anq
from repro.kernels import decode_attention as da
from repro.kernels import dynamic_quant as dq
from repro.kernels import flash_attention as fa
from repro.kernels import fused_embed as fe
from repro.kernels import page_write as pw
from repro.kernels import quant_linear as ql

# bert-base: d_model 768, 12 heads of 64, ffn 3072, vocab 21128, 512
# positions, 2 segments; a batch of 8 x 512 tokens
BERT = dict(M=4096, D=768, F=3072, V=21128, P=512, B=8, H=12, S=512, hd=64)
# qwen2-0.5b: d_model 896, ffn 4864, 14 query / 2 kv heads of 64
QWEN = dict(D=896, F=4864, Hkv=2, g=7, hd=64)
# the chat-decode cell's pool: 32 slots of 768 tokens over 16-token pages
CHAT = dict(B=32, max_len=768, ps=16, NP=32 * 48)
# deepseek-v2-lite-ep4: 16 held experts of width 1408 over d_model 2048; the
# moe-chat cell's 64 slots of 768 tokens
DSV2 = dict(E=16, D=2048, F=1408, B=64, max_len=768, ps=16)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _assert_kernel(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


I8, F32, I32 = jnp.int8, jnp.float32, jnp.int32


@pytest.mark.parametrize("width", ["bert_ffn_in", "qwen_ffn_in_m4"])
def test_quant_linear_static_scale_gelu(one_chip, width):
    M, K, N = ((BERT["M"], BERT["D"], BERT["F"]) if width == "bert_ffn_in"
               else (4, QWEN["D"], QWEN["F"]))
    _assert_kernel(
        one_chip,
        lambda x, w, ws, xs, b: ql.quant_linear(
            x, w, ws, xs, bias=b, act="gelu", out_dtype=F32,
            interpret=False),
        ((M, K), I8), ((K, N), I8), ((N,), F32), ((), F32), ((N,), F32))


def test_int8_kernels_under_highest_matmul_precision(one_chip):
    """A process-wide "highest" matmul precision must not reach the int8
    contractions, which Mosaic refuses at an fp32 contract precision."""
    B, S = 2, 128
    shape = (B, BERT["H"], S, BERT["hd"])
    M, K, N = B * S, BERT["D"], BERT["F"]
    with jax.default_matmul_precision("highest"):
        _assert_kernel(
            one_chip,
            lambda x, w, ws, xs: ql.quant_linear(x, w, ws, xs,
                                                 interpret=False),
            ((M, K), I8), ((K, N), I8), ((N,), F32), ((), F32))
        _assert_kernel(
            one_chip,
            lambda q, k, v, kp, s: fa.quant_flash_attention(
                q, k, v, kp, q_scale=s, k_scale=s, p_scale=s, v_scale=s,
                interpret=False),
            (shape, I8), (shape, I8), (shape, I8), ((B, S), I32), ((), F32))


def test_quant_linear_per_token_requant(one_chip):
    M, K, N = BERT["M"], BERT["D"], BERT["F"]
    _assert_kernel(
        one_chip,
        lambda x, w, ws, xs, os_: ql.quant_linear(
            x, w, ws, xs, out_scale=os_, interpret=False),
        ((M, K), I8), ((K, N), I8), ((N,), F32), ((M, 1), F32), ((), F32))


def test_dynamic_quant(one_chip):
    _assert_kernel(one_chip,
                   lambda x: dq.dynamic_quant(x, interpret=False),
                   ((BERT["M"], BERT["D"]), F32))


@pytest.mark.parametrize("delta", ["float", "int8"])
def test_addnorm_quant(one_chip, delta):
    M, D = BERT["M"], BERT["D"]
    int8 = delta == "int8"
    _assert_kernel(
        one_chip,
        lambda x, r, b, g, be, s, si: anq.addnorm_quant(
            x, r, b, g, be, s, x_in_scale=si if int8 else None,
            interpret=False),
        ((M, D), I8 if int8 else F32), ((M, D), F32), ((D,), F32),
        ((D,), F32), ((D,), F32), ((), F32), ((), F32))


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16])
def test_fused_embed(one_chip, dtype):
    N, D = BERT["M"], BERT["D"]
    _assert_kernel(
        one_chip,
        lambda t, tt, pt, st, s, p: fe.fused_embed(
            t, tt, pt, st, s, positions=p, interpret=False),
        ((N,), I32), ((BERT["V"], D), dtype), ((BERT["P"], D), dtype),
        ((2, D), dtype), ((N,), I32), ((N,), I32))


def test_flash_attention(one_chip):
    shape = (BERT["B"], BERT["H"], BERT["S"], BERT["hd"])
    _assert_kernel(one_chip,
                   lambda q, k, v: fa.flash_attention(q, k, v,
                                                      interpret=False),
                   (shape, F32), (shape, F32), (shape, F32))


@pytest.mark.parametrize("requant", [False, True])
def test_quant_flash_attention_batched(one_chip, requant):
    B, S = BERT["B"], BERT["S"]
    shape = (B, BERT["H"], S, BERT["hd"])
    _assert_kernel(
        one_chip,
        lambda q, k, v, kp, s: fa.quant_flash_attention(
            q, k, v, kp, q_scale=s, k_scale=s, p_scale=s, v_scale=s,
            o_scale=s if requant else None, interpret=False),
        (shape, I8), (shape, I8), (shape, I8), ((B, S), I32), ((), F32))


DECODE_MODES = ["per_head", "per_token", "per_head_p_scale"]


def _assert_decode_attention(one_chip, mode, layers):
    """``decode_attention`` at the chat-decode cell's geometry (32 slots of
    48 pages of 16 tokens, qwen2-0.5b's heads), its int8 pool padded to
    whole lanes as the runtime holds it on the chip: one layer's pool, or a
    stack of ``layers`` read at the layer an operand names."""
    B, Hkv, g, hd, ps = CHAT["B"], QWEN["Hkv"], QWEN["g"], QWEN["hd"], \
        CHAT["ps"]
    NP, pps = CHAT["NP"], CHAT["max_len"] // CHAT["ps"]
    per_head, quant_p = mode != "per_token", mode == "per_head_p_scale"
    stack = (layers,) if layers else ()
    scale_shape = (Hkv,) if per_head else stack + (NP, Hkv, 128)
    _assert_kernel(
        one_chip,
        lambda q, k, v, pt, ln, ks, vs, p, ly: da.decode_attention(
            q, k, v, pt, ln, k_scale=ks, v_scale=vs, per_head=per_head,
            p_scale=p if quant_p else None, layer=ly if layers else None,
            interpret=False),
        ((B, Hkv, g, hd), F32), (stack + (NP, Hkv, ps, 128), I8),
        (stack + (NP, Hkv, ps, 128), I8), ((B, pps), I32), ((B,), I32),
        (scale_shape, F32), (scale_shape, F32), ((), F32), ((), I32))


@pytest.mark.parametrize("mode", DECODE_MODES)
def test_decode_attention(one_chip, mode):
    _assert_decode_attention(one_chip, mode, layers=0)


@pytest.mark.parametrize("lanes", [1, 128])
def test_page_write(one_chip, lanes):
    """The in-place page write over a whole 24-layer stack of the cell's
    pool, K/V pages and their per-token scales, unpadded and padded to
    whole lanes (as the runtime holds it)."""
    B, Hkv, hd, ps, NP = CHAT["B"], QWEN["Hkv"], QWEN["hd"], CHAT["ps"], \
        CHAT["NP"]
    wide = lambda n: -(-n // lanes) * lanes          # noqa: E731
    _assert_kernel(
        one_chip,
        lambda k, v, ks, vs, rk, rv, rks, rvs, ly, pg, rw: pw.page_write(
            (k, v, ks, vs), (rk, rv, rks, rvs), ly, pg, rw,
            interpret=False),
        ((24, NP, Hkv, ps, wide(hd)), I8), ((24, NP, Hkv, ps, wide(hd)), I8),
        ((24, NP, Hkv, wide(ps)), F32), ((24, NP, Hkv, wide(ps)), F32),
        ((B, Hkv, hd), I8), ((B, Hkv, hd), I8), ((B, Hkv), F32),
        ((B, Hkv), F32), ((), I32), ((B,), I32), ((B,), I32))


def _decode_step_text(monkeypatch, scheme, on, rows, mesh=None):
    """Compiled HLO text of the serving runtime's decode step at the
    chat-decode cell's geometry (qwen2-0.5b widths, two layers, fused
    backend, int8 pages as the runtime pads them for the chip), with the
    caches; ``on`` / ``rows`` place the caches and the per-slot operands."""
    from repro.configs import get_config
    from repro.core.plan import PrecisionPlan
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.serve.runtime import Runtime
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("qwen2-0.5b").replace(num_layers=2)
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)

    def params_fn():
        params = T.init_params(jax.random.PRNGKey(0), cfg, policy)
        if scheme == "int8_per_head":       # calibrated per-head scales
            for group in params["groups"]:
                for lp in group["layers"]:
                    for key in ("kc_scale", "vc_scale"):
                        lp["attn"][key] = jnp.full(
                            (cfg.num_layers, cfg.num_kv_heads), 0.05)
        return params

    def placed(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)
    B = CHAT["B"]
    rt = Runtime(cfg, plan, backend="fused", compute_dtype=F32, mesh=mesh)
    assert rt.backend.page_lanes() == 128   # compiled: lane-dense pages
    params = placed(jax.eval_shape(params_fn), rows if mesh is None
                    else jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec()))
    caches = placed(jax.eval_shape(lambda: T.init_caches(
        cfg, plan, B, CHAT["max_len"], F32, page_size=CHAT["ps"],
        kv_schemes=(scheme,) * cfg.num_layers,
        lanes=rt.backend.page_lanes())), on)
    _, step = rt._decode_executable(params, caches)
    args = [jax.ShapeDtypeStruct(s, d, sharding=rows) for s, d in
            (((B, 1), I32), ((B,), I32), ((B,), jnp.bool_),
             ((B, CHAT["max_len"] // CHAT["ps"]), I32))]
    return step.lower(params, caches, *args).compile().as_text(), caches, rt


@pytest.mark.parametrize("scheme", ["int8_per_token", "int8_per_head"])
def test_decode_step_keeps_the_pool_in_place(one_chip, monkeypatch, scheme):
    """The decode step holds no relayout copy of a page-pool leaf
    (``pool_copies`` 0), and ``decode_attention`` is still one Pallas
    call in the layer scan's body, with its ``f32[slots, kv_heads, group,
    head_dim]`` result."""
    from repro.serve.runtime import pool_copies
    text, caches, _ = _decode_step_text(monkeypatch, scheme, one_chip,
                                        one_chip)
    assert pool_copies(text, caches) == 0
    attn = [line for line in text.splitlines()
            if "%decode_attention" in line and "custom-call(" in line]
    assert len(attn) == 1 and "tpu_custom_call" in attn[0]
    assert all(f"f32[{CHAT['B']},{QWEN['Hkv']},{QWEN['g']},{QWEN['hd']}]"
               in line for line in attn)


def test_decode_step_per_device_keeps_the_pool_in_place(topo, monkeypatch):
    """On a data-parallel mesh of the described four chips each device
    runs the step on its share of the pool, and no device relayouts it."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.serve.runtime import pool_copies
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    text, caches, rt = _decode_step_text(
        monkeypatch, "int8_per_token",
        NamedSharding(mesh, PartitionSpec(None, "data")),
        NamedSharding(mesh, PartitionSpec("data")), mesh=mesh)
    assert rt.shards == 4 and "tpu_custom_call" in text
    assert pool_copies(text, caches, rt.shards) == 0


@pytest.mark.parametrize("mode", DECODE_MODES)
def test_decode_attention_on_a_lane_padded_stack(one_chip, mode):
    """``decode_attention`` reading one layer of the cell's whole 24-layer
    pool, padded to whole lanes, with the layer as an operand."""
    _assert_decode_attention(one_chip, mode, layers=24)


@pytest.mark.parametrize("gemm", ["gate", "down"])
def test_quant_expert_gemm(one_chip, gemm):
    """The grouped expert kernel over the 16 held experts' 64-row buffers,
    in both GEMM orientations of a SwiGLU expert."""
    E, M = DSV2["E"], DSV2["B"]
    K, N = ((DSV2["D"], DSV2["F"]) if gemm == "gate"
            else (DSV2["F"], DSV2["D"]))
    _assert_kernel(
        one_chip,
        lambda x, w, ws, xs: ql.quant_expert_gemm(x, w, ws, xs,
                                                  interpret=False),
        ((E, M, K), I8), ((E, K, N), I8), ((E, N), F32), ((E, M, 1), F32))


def test_deepseek_v2_lite_decode_step(one_chip, monkeypatch):
    """The serving runtime's decode step of ``deepseek-v2-lite-ep4`` at its
    published widths, cut to the dense layer and two MoE layers, on the
    moe-chat cell's 64 slots (ffn plan, fused backend, float latent
    pages): every expert GEMM is the grouped kernel, and the step returns
    the routed-pick count beside the logits and the caches."""
    from repro.configs import get_config
    from repro.core.plan import PrecisionPlan
    from repro.core.precision import make_policy
    from repro.kernels import ops
    from repro.launch.dryrun import abstract_stats
    from repro.models import transformer as T
    from repro.quant import ptq
    from repro.serve.runtime import Runtime
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("deepseek-v2-lite-ep4").replace(num_layers=3)
    precision = make_policy(cfg, "ffn")

    def params_fn():
        params = T.init_params(jax.random.PRNGKey(0), cfg,
                               PrecisionPlan.full_float(cfg.num_layers,
                                                        "float32"))
        return ptq.apply_plan(params, cfg, precision,
                              abstract_stats(cfg))[0]

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)
    plan = T.build_plan(cfg, precision)
    rt = Runtime(cfg, plan, precision=precision, backend="fused",
                 compute_dtype=F32)
    B, max_len, ps = DSV2["B"], DSV2["max_len"], DSV2["ps"]
    caches = placed(jax.eval_shape(lambda: T.init_caches(
        cfg, plan, B, max_len, F32, page_size=ps,
        kv_schemes=("float",) * cfg.num_layers,
        lanes=rt.backend.page_lanes())))
    _, step = rt._decode_executable(placed(jax.eval_shape(params_fn)),
                                    caches)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (((B, 1), I32), ((B,), I32), ((B,), jnp.bool_),
             ((B, max_len // ps), I32))]
    lowered = step.lower(placed(jax.eval_shape(params_fn)), caches, *args)
    logits, _, routed = lowered.out_info
    assert logits.shape == (B, cfg.vocab_size) and routed.shape == ()
    text = lowered.compile().as_text()
    experts = [line for line in text.splitlines()
               if "%quant_expert_gemm" in line and "custom-call(" in line]
    # the gate, up and down GEMMs of the scanned MoE layer's body
    assert len(experts) == 3
    assert all("tpu_custom_call" in line for line in experts)
