"""Pallas TPU kernel: flash attention (online softmax) for the 32k shapes.

The hot path for ``prefill_32k``: blockwise attention with the running
(m, l, acc) online-softmax state held in VMEM scratch across the key grid
axis, so the (Sq, Sk) score matrix never touches HBM. Supports causal and
sliding-window masks (positions are block-aligned), GQA (queries carry more
heads than keys — the head grid indexes kv heads via integer division) and
gemma-style score softcapping.

Grid: (batch * q_heads, Sq/bq, Sk/bk), key axis innermost ("arbitrary"
semantics — it carries the accumulator). Causal masking skips fully-masked
key blocks via ``pl.when`` on the block indices, halving compute for causal
prefill.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_linear import INT8_PRECISION, fit_block

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
INT8_MIN = -128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: Optional[float], bq: int, bk: int, nk: int):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level mask decision: last query position vs first key position.
    q_lo = qb * bq
    k_lo = kb * bk
    run = None
    if causal:
        run = k_lo <= q_lo + bq - 1                # else fully masked
    if window is not None:
        in_win = k_lo + bk - 1 > q_lo - window
        run = in_win if run is None else jnp.logical_and(run, in_win)

    def _body():
        q = q_ref[0].astype(jnp.float32) * scale   # (bq, d)
        k = k_ref[0].astype(jnp.float32)           # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = kpos <= qpos
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)            # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    if run is None:
        _body()
    else:
        pl.when(run)(_body)

    @pl.when(kb == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _quant_kernel(q_ref, k_ref, v_ref, kpos_ref, qs_ref, ks_ref, ps_ref,
                  vs_ref, os_ref, o_ref, *,
                  softcap: Optional[float], requant: bool):
    """Fully-int8 encoder attention for one (batch*head, q-block) tile.

    QK^T and P·V run int8 on the MXU; the softmax itself is exact f32 (it
    is a reduction, not a GEMM), but its *output* is quantized with the
    asymmetric unsigned scheme (zero point -128, scale = amax/255 — all
    256 code points land in [0, 1]) before the value matmul, exactly
    mirroring ``quant_bmm(..., unsigned_a=True)`` in the reference path.
    With ``requant`` the epilogue emits int8 at the attn_out GEMM's
    calibrated activation scale — the whole-layer int8 span's first hop.
    """
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            precision=INT8_PRECISION,
                            preferred_element_type=jnp.int32)
    s = s.astype(jnp.float32) * (qs_ref[...] * ks_ref[...])
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(kpos_ref[0] >= 0, s, NEG_INF)         # validity mask
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)          # exact softmax
    # unsigned-int8 softmax epilogue: codes = round(p / ps) + INT8_MIN
    pq = jnp.clip(jnp.round(p / ps_ref[...]) + INT8_MIN, -128, 127) \
        .astype(jnp.int8)
    v = v_ref[0]
    acc = jax.lax.dot_general(pq, v, (((1,), (0,)), ((), ())),
                              precision=INT8_PRECISION,
                              preferred_element_type=jnp.int32)
    # zero-point correction: dot(codes - zp, v) = dot(codes, v) - zp*sum(v)
    vsum = jnp.sum(v.astype(jnp.int32), axis=0, keepdims=True)
    acc = acc - INT8_MIN * vsum
    o = acc.astype(jnp.float32) * (ps_ref[...] * vs_ref[...])
    if requant:
        o_ref[0] = jnp.clip(jnp.round(o / os_ref[...]), -128, 127) \
            .astype(jnp.int8)
    else:
        o_ref[0] = o.astype(o_ref.dtype)


def quant_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          k_pos: jax.Array, *,
                          q_scale, k_scale, p_scale, v_scale,
                          o_scale=None, softcap: Optional[float] = None,
                          out_dtype=jnp.float32, bq: int = 256,
                          interpret: bool = False) -> jax.Array:
    """Fully-quantized bidirectional (encoder) attention.

    q: (B, Hq, Sq, d) int8 — quantized from ``q_float * rsqrt(d)`` at the
    calibrated ``q`` scale, so no further score scaling happens in-kernel;
    k, v: (B, Hkv, Sk, d) int8 with Hq % Hkv == 0 (GQA: the head grid
    indexes kv heads by integer division, like the float flash kernel);
    k_pos: (B, Sk) int32 key positions, -1 = padding (masked). The four
    scheme scales are scalar **operands**; ``o_scale`` (also an operand)
    switches the epilogue to int8 output at the attn_out GEMM's activation
    scale. The whole key axis is resident per tile (encoder lengths; no
    online-softmax state), queries tile by ``bq``.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    g = Hq // Hkv
    bq = fit_block(Sq, bq)
    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    # (B, 1, Sk): a (1, Sk) block is then the array's whole last two dims,
    # which Mosaic accepts for any batch (a (1, Sk) block of (B, Sk) is not)
    kpos = jnp.broadcast_to(jnp.asarray(k_pos, jnp.int32).reshape(-1, 1, Sk),
                            (B, 1, Sk))
    requant = o_scale is not None
    scalars = [jnp.asarray(x, jnp.float32).reshape(1, 1)
               for x in (q_scale, k_scale, p_scale, v_scale,
                         o_scale if requant else 1.0)]
    kernel = functools.partial(_quant_kernel, softcap=softcap,
                               requant=requant)
    scalar_spec = pl.BlockSpec((1, 1), lambda h, i: (0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i, g=g: (h // g, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda h, i, g=g: (h // g, 0, 0)),
            pl.BlockSpec((1, 1, Sk), lambda h, i, H=Hq: (h // H, 0, 0)),
            scalar_spec, scalar_spec, scalar_spec, scalar_spec, scalar_spec,
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (B * Hq, Sq, D), jnp.int8 if requant else out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qf, kf, vf, kpos, *scalars)
    return out.reshape(B, Hq, Sq, D)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    bq: int = 512, bk: int = 512,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, d); k, v: (B, Hkv, Sk, d) with Hq % Hkv == 0.
    Returns (B, Hq, Sq, d).

    ``causal`` defaults **off**: the paper's workloads are encoder-only
    (bidirectional) — decoder callers must opt in with ``causal=True``
    explicitly at the call site.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    g = Hq // Hkv
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    nk = Sk // bk
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, g=g: (h // g, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j, g=g: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, D)
