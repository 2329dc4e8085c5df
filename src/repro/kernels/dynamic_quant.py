"""Pallas TPU kernel: per-token dynamic quantization (beyond-paper).

The paper uses static per-tensor activation scales from offline calibration.
Modern W8A8 serving quantizes activations per-token at runtime instead —
one extra row-max pass, no calibration drift. Fused here: amax reduction +
scale + quantize in a single VMEM-resident pass per row block, emitting the
int8 tensor and the (M, 1) f32 row scales the downstream GEMM consumes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_linear import fit_block

_EPS = 1e-8


def _kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.round(x / scale)
    q_ref[...] = jnp.clip(q, -128, 127).astype(jnp.int8)
    s_ref[...] = scale


def dynamic_quant(x: jax.Array, *, bm: int = 256,
                  interpret: bool = False):
    """x: (M, D) float -> (q (M, D) int8, scale (M, 1) f32)."""
    M, D = x.shape
    bm = fit_block(M, bm)   # ragged row counts: shrink to a divisor
    q, s = pl.pallas_call(
        _kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((bm, D), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, D), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, D), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return q, s
