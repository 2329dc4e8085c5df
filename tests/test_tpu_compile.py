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
from repro.kernels import quant_linear as ql

# bert-base: d_model 768, 12 heads of 64, ffn 3072, vocab 21128, 512
# positions, 2 segments; a batch of 8 x 512 tokens
BERT = dict(M=4096, D=768, F=3072, V=21128, P=512, B=8, H=12, S=512, hd=64)
# qwen2-0.5b: d_model 896, ffn 4864, 14 query / 2 kv heads of 64; 4 decode
# slots over 16-token pages, 512 tokens per slot
QWEN = dict(D=896, F=4864, B=4, Hkv=2, g=7, hd=64, ps=16, pps=32)


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


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


@pytest.mark.parametrize("mode", ["per_head", "per_token", "per_head_p_scale"])
def test_decode_attention(one_chip, mode):
    B, Hkv, g, hd = QWEN["B"], QWEN["Hkv"], QWEN["g"], QWEN["hd"]
    ps, pps = QWEN["ps"], QWEN["pps"]
    NP = B * pps
    per_head = mode != "per_token"
    scale_shape = (Hkv,) if per_head else (NP, Hkv, ps)
    quant_p = mode == "per_head_p_scale"
    _assert_kernel(
        one_chip,
        lambda q, k, v, pt, ln, ks, vs, p: da.decode_attention(
            q, k, v, pt, ln, k_scale=ks, v_scale=vs, per_head=per_head,
            p_scale=p if quant_p else None, interpret=False),
        ((B, Hkv, g, hd), F32), ((NP, Hkv, ps, hd), I8),
        ((NP, Hkv, ps, hd), I8), ((B, pps), I32), ((B,), I32),
        (scale_shape, F32), (scale_shape, F32), ((), F32))
