"""Pallas TPU kernel: fused W8A8 GEMM epilogue — the paper's "big kernel".

SAMP's CUDA version fuses Quant/DeQuant into AddBias/AddResidual/LayerNorm
so inter-kernel dataflow stays INT8 (paper Figure 2, green arrows). The TPU
translation (DESIGN.md §2): the win is HBM round-trips, so this kernel keeps
the int32 accumulator in VMEM scratch across the K grid axis and applies
dequant + bias + activation + (optional) requantize **in-register** before
the single HBM write-back. In Fully-Quant mode the layer boundary tensor is
int8 — 1 byte/elt of HBM traffic instead of 2.

The activation scale is a **per-row operand** (an (M, 1) f32 array), not a
compile-time constant, so one compiled kernel serves both of the plan's
activation schemes: static per-tensor scales (the paper's calibrated path —
the caller broadcasts the scalar) and per-token dynamic scales (the row
scales emitted by the ``dynamic_quant`` kernel). Traced scales also mean a
re-calibration never forces a recompile.

Tiling: (bm x bk) @ (bk x bn) MXU tiles; block dims are shrunk to the
largest divisor of the actual dims (128-aligned shapes keep the full
(8/32, 128) TPU tile grid).
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The one activation table shared by the kernel epilogue, the reference
# dense path (repro.models.layers) and the jnp oracle (kernels/ref.py):
# fused-vs-reference parity requires a single definition.
ACTIVATIONS = {
    None: lambda x: x,
    "silu": jax.nn.silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
}


#: int8 MXU contractions are exact and take no float precision. Pinning
#: DEFAULT keeps a process-wide ``jax_default_matmul_precision`` (e.g.
#: "highest") out of them: Mosaic rejects an fp32 contract precision on
#: int8 operands.
INT8_PRECISION = jax.lax.Precision.DEFAULT


def fit_block(n: int, b: int) -> int:
    """Largest divisor of ``n`` that is <= the requested block size ``b``
    (power-of-two / 128-multiple dims keep the requested tiling; ragged
    dims shrink instead of asserting)."""
    b = min(b, n)
    while n % b:
        b -= 1
    return b


def _kernel(x_ref, w_ref, ws_ref, xs_ref, b_ref, os_ref, o_ref, acc_ref, *,
            nk: int, act: Optional[str], requant: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=INT8_PRECISION, preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32)
        y = y * (xs_ref[...] * ws_ref[...])      # dequant: (bm,1) x (1,bn)
        y = y + b_ref[...]
        y = ACTIVATIONS[act](y)
        if requant:                              # requantize: int8 stays int8
            q = jnp.round(y / os_ref[...])
            o_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)
        else:
            o_ref[...] = y.astype(o_ref.dtype)


def quant_linear(x_q: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                 x_scale: Union[float, jax.Array], *,
                 bias: Optional[jax.Array] = None,
                 act: Optional[str] = None,
                 out_scale: Union[float, jax.Array, None] = None,
                 out_dtype=jnp.bfloat16,
                 bm: int = 128, bn: int = 128, bk: int = 128,
                 interpret: bool = False) -> jax.Array:
    """y = epilogue((x_q @ w_q) * x_scale * w_scale + bias).

    x_q: (M, K) int8; w_q: (K, N) int8; w_scale: (N,) f32 per-channel;
    x_scale: a python float / scalar array (static per-tensor activation
    scale — the paper's calibrated scheme) or an (M,) / (M, 1) array of
    per-token dynamic scales. ``out_scale`` requantizes the output to int8
    for int8 inter-layer dataflow; like ``x_scale`` it is a scalar
    **operand** (only its presence is structural), so recalibrating the
    consumer's scale never retraces.
    """
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    bm, bn, bk = fit_block(M, bm), fit_block(N, bn), fit_block(K, bk)
    nk = K // bk
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    xs = jnp.asarray(x_scale, jnp.float32)
    if xs.ndim == 0:
        xs = jnp.broadcast_to(xs.reshape(1, 1), (M, 1))
    else:
        xs = xs.reshape(M, 1)
    requant = out_scale is not None
    os_op = jnp.asarray(out_scale if requant else 1.0,
                        jnp.float32).reshape(1, 1)
    kernel = functools.partial(_kernel, nk=nk, act=act, requant=requant)
    out = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (M, N), jnp.int8 if requant else out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, w_scale.reshape(1, N).astype(jnp.float32), xs,
      bias.reshape(1, N).astype(jnp.float32), os_op)
    return out
