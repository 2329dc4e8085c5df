"""Pallas TPU kernel: fused AddResidual + AddBias + Norm + Quantize.

The paper's Layer-fusion contribution (§2.2/§3.2): Quant/DeQuant ops folded
into the AddResidual/AddBias/LayerNorm "big kernel" so the tensor crossing
kernel (= HBM) boundaries is int8. One pass over the rows computes

    h   = x + residual + bias            (f32, the residual carry)
    y   = norm(h) * gamma (+ beta)       (rmsnorm or layernorm)
    q   = clip(round(y / x_scale))       (int8, feeds the next quant GEMM)

and writes both h (needed for the next residual add) and q. Row-parallel:
block = (bm, D) with the full feature dim resident in VMEM (D <= a few K for
every assigned arch, far under the ~16 MB VMEM budget at bm = 256).

``x_scale`` — the static activation scale of the *consuming* GEMM — is a
scalar **operand**, not a compile-time constant, so recalibrating a plan (or
running the kernel under a jitted forward whose params are call arguments)
never forces a recompile.
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_linear import fit_block


def _kernel(x_ref, res_ref, b_ref, g_ref, beta_ref, s_ref, xs_ref, h_ref,
            q_ref, *, kind: str, eps: float):
    # ``x`` may arrive int8 (the attn_out GEMM's requantized output under a
    # whole-layer int8 span); ``xs`` dequantizes it in-register — float
    # deltas carry xs == 1.0 and the multiply is exact.
    x = x_ref[...].astype(jnp.float32) * xs_ref[...]
    h = x + res_ref[...].astype(jnp.float32) + b_ref[...]
    if kind == "layernorm":
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + eps) * g_ref[...] + beta_ref[...]
    else:
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        y = h * jax.lax.rsqrt(var + eps) * g_ref[...]
    h_ref[...] = h.astype(h_ref.dtype)
    q = jnp.round(y / s_ref[...])
    q_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)


def addnorm_quant(x: jax.Array, residual: jax.Array, bias: jax.Array,
                  gamma: jax.Array, beta: jax.Array | None,
                  x_scale: Union[float, jax.Array],
                  *, x_in_scale: Union[float, jax.Array, None] = None,
                  kind: str = "layernorm", eps: float = 1e-6,
                  bm: int = 256, interpret: bool = False):
    """x, residual: (M, D); bias/gamma/beta: (D,); x_scale: python float or
    scalar array. Returns (h f32/bf16, q int8). ``kind``: 'layernorm' |
    'rmsnorm'.

    ``x`` may be int8 (the producing GEMM requantized it — the whole-layer
    int8 span), in which case ``x_in_scale`` is its dequantization scale;
    both scales are scalar operands, so neither a recalibration nor a
    scale swap retraces.
    """
    M, D = x.shape
    bm = fit_block(M, bm)
    if beta is None:
        beta = jnp.zeros((D,), jnp.float32)
    if x.dtype == jnp.int8 and x_in_scale is None:
        raise ValueError("int8 delta input needs x_in_scale (its dequant "
                         "scale)")
    xs_in = jnp.asarray(1.0 if x_in_scale is None else x_in_scale,
                        jnp.float32).reshape(1, 1)
    out_dtype = residual.dtype if x.dtype == jnp.int8 else x.dtype
    kernel = functools.partial(_kernel, kind=kind, eps=eps)
    row = pl.BlockSpec((bm, D), lambda i: (i, 0))
    vec = pl.BlockSpec((1, D), lambda i: (0, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    h, q = pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[row, row, vec, vec, vec, scalar, scalar],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((M, D), out_dtype),
                   jax.ShapeDtypeStruct((M, D), jnp.int8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, residual, bias.reshape(1, D).astype(jnp.float32),
      gamma.reshape(1, D).astype(jnp.float32),
      beta.reshape(1, D).astype(jnp.float32),
      jnp.asarray(x_scale, jnp.float32).reshape(1, 1), xs_in)
    return h, q
