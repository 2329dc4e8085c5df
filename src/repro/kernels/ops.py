"""jit'd public wrappers for the Pallas kernels.

On TPU these lower to Mosaic; on any other platform they run in interpret
mode (the kernel body executes grid step by grid step through XLA — the
correctness path the CPU test suite uses). The choice is made when a
wrapper is traced, from the platform the computation runs on, so model
code calls the same entry points everywhere and importing this module
touches no JAX backend.

Activation scales are **operands** (traced arrays), not static arguments:
the serving runtime jits the whole forward with params as call arguments,
so calibrated scales must flow through the kernels as data — swapping a
recalibrated checkpoint or a per-token dynamic scale never retraces.

These wrappers are the only kernel entry points the compute-backend layer
(:mod:`repro.kernels.backend`) dispatches to; model code selects between
them and the reference XLA ops per block via the ``BACKENDS`` registry.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels import addnorm_quant as _anq
from repro.kernels import decode_attention as _da
from repro.kernels import dynamic_quant as _dq
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_embed as _fe
from repro.kernels import quant_linear as _ql


def _interpret() -> bool:
    """Interpret mode everywhere but a TPU, where Mosaic compiles the
    kernels. Called at trace time, never at import."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=(
    "act", "out_dtype", "bm", "bn", "bk"))
def quant_linear(x_q, w_q, w_scale, x_scale: Union[float, jax.Array], *,
                 bias=None, act: Optional[str] = None,
                 out_scale: Union[float, jax.Array, None] = None,
                 out_dtype=jnp.bfloat16, bm=128, bn=128, bk=128):
    """Fused W8A8 GEMM; ``x_scale`` is a scalar (static per-tensor) or
    (M,)/(M, 1) per-token operand. ``out_scale`` (requantize-to-int8
    epilogue) is likewise an operand — only its presence/absence is
    structural."""
    return _ql.quant_linear(x_q, w_q, w_scale, x_scale, bias=bias, act=act,
                            out_scale=out_scale, out_dtype=out_dtype,
                            bm=bm, bn=bn, bk=bk,
                            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("kind", "eps", "bm"))
def addnorm_quant(x, residual, bias, gamma, beta,
                  x_scale: Union[float, jax.Array], *,
                  x_in_scale: Union[float, jax.Array, None] = None,
                  kind: str = "layernorm", eps: float = 1e-6, bm: int = 256):
    """Fused residual add + norm + requantize; ``x_scale`` is a scalar
    operand (the consuming GEMM's static activation scale). ``x`` may be
    int8 (a requantized GEMM output), dequantized in-kernel by the
    ``x_in_scale`` operand."""
    return _anq.addnorm_quant(x, residual, bias, gamma, beta, x_scale,
                              x_in_scale=x_in_scale, kind=kind, eps=eps,
                              bm=bm, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "out_dtype"))
def fused_embed(tokens, tok_table, pos_table, seg_table=None, segments=None,
                *, positions=None, scale: float = 1.0,
                out_dtype=jnp.float32):
    """Fused token+position+segment gather; ``positions`` (N,) overrides the
    default row-major ``arange(N) mod S`` position stream."""
    return _fe.fused_embed(tokens, tok_table, pos_table, seg_table, segments,
                           positions=positions, scale=scale,
                           out_dtype=out_dtype, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bm",))
def dynamic_quant(x, *, bm: int = 256):
    return _dq.dynamic_quant(x, bm=bm, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("out_dtype", "bm", "bn", "bk"))
def quant_expert_gemm(xe, w_q, w_scale, xs=None, *, out_dtype=jnp.float32,
                      bm: int = 128, bn: int = 128, bk: int = 128):
    """Batched per-expert W8A8 GEMM: a routed capacity buffer
    ``xe (..., E, C, D)`` against an int8 expert stack ``w_q (E, D, F)``
    -> ``(..., E, C, F)``.

    Per-expert scales are **operands**: ``w_scale`` broadcastable to
    (E, 1, F) (per-expert-per-channel, the v4 ``experts`` family layout) and
    ``xs`` broadcastable to (E, 1, 1) (per-expert static activation scales;
    ``None`` selects per-token dynamic quantization via ``dynamic_quant``).
    The expert axis is a static Python grid — expert count is model
    structure, not data — so each expert's token shard runs through one
    fused ``quant_linear`` with exactly its own scale operands.
    """
    from repro.core.quantize import quantize, quantize_per_token
    E, D, F = w_q.shape
    lead = xe.shape[:-3]
    ws = jnp.asarray(w_scale, jnp.float32)
    ws = jnp.broadcast_to(ws.reshape((1, 1, -1) if ws.ndim < 3 else ws.shape),
                          (E, 1, F)).reshape(E, F)
    # Quantize the whole routed buffer in ONE op, exactly the subgraph the
    # reference einsum path builds, then slice codes per expert. Quantizing
    # per-expert slices separately lets XLA fuse the round differently
    # (reciprocal-multiply vs divide), and a ±1 code flip at a rounding
    # boundary is an O(scale) output step — which the MoE router then
    # amplifies into a different top-k choice. Identical subgraph ->
    # identical codes -> backend choice never moves the routing.
    if xs is not None:
        xs_b = jnp.asarray(xs, jnp.float32)
        if xs_b.ndim == 0:                               # legacy scalar plan
            codes = quantize(xe, xs_b)
            x_scales = [xs_b] * E
        else:
            xs3 = jnp.broadcast_to(xs_b.reshape(-1, 1, 1), (E, 1, 1))
            codes = quantize(xe, xs3)
            x_scales = [xs3[e, 0, 0] for e in range(E)]
    else:
        xq = quantize_per_token(xe)                      # (..., E, C, 1)
        codes = xq.values
        sc4 = xq.scale.reshape((-1,) + xq.scale.shape[-3:])
        x_scales = None
    x4 = codes.reshape((-1,) + codes.shape[-3:])         # (G, E, C, D) int8
    G, _, C, _ = x4.shape
    outs = []
    for e in range(E):
        rows_q = x4[:, e].reshape(G * C, D)
        x_scale = (x_scales[e] if x_scales is not None
                   else sc4[:, e].reshape(G * C, 1))
        y = quant_linear(rows_q, w_q[e], ws[e], x_scale, bias=None, act=None,
                         out_scale=None, out_dtype=out_dtype,
                         bm=bm, bn=bn, bk=bk)
        outs.append(y.reshape(G, C, F))
    return jnp.stack(outs, axis=1).reshape(lead + (E, C, F))


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = False,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None, bq: int = 512,
                    bk: int = 512):
    """Flash attention. ``causal`` defaults off (the paper's encoder-only
    workloads are bidirectional); decoder paths must pass ``causal=True``
    explicitly."""
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, bq=bq, bk=bk,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=(
    "softcap", "out_dtype", "bq"))
def quant_flash_attention(q, k, v, k_pos, *, q_scale, k_scale, p_scale,
                          v_scale, o_scale=None,
                          softcap: Optional[float] = None,
                          out_dtype=jnp.float32, bq: int = 256):
    """Fully-int8 encoder attention with the unsigned-uint8 softmax
    epilogue. All five scheme scales are scalar **operands** —
    recalibrating a plan's softmax/attention scales never retraces; only
    ``o_scale``'s presence (int8 vs float output) is structural."""
    return _fa.quant_flash_attention(q, k, v, k_pos, q_scale=q_scale,
                                     k_scale=k_scale, p_scale=p_scale,
                                     v_scale=v_scale, o_scale=o_scale,
                                     softcap=softcap, out_dtype=out_dtype,
                                     bq=bq, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("per_head", "scale", "softcap"))
def decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                     k_scale, v_scale, per_head: bool,
                     scale: Optional[float] = None,
                     softcap: Optional[float] = None,
                     p_scale=None):
    """Paged int8-KV decode attention (single query token per slot).

    ``page_table``/``lengths`` are operands — slots churn every step and
    must not retrace; the kv scheme (``per_head``) and page geometry are
    static and baked into the executable key by the serving runtime.
    ``p_scale`` (the plan's ``softmax='uint8'`` scheme) is a scalar
    operand; its presence selects the two-pass quantized-softmax grid."""
    return _da.decode_attention(q, k_pages, v_pages, page_table, lengths,
                                k_scale=k_scale, v_scale=v_scale,
                                per_head=per_head, scale=scale,
                                softcap=softcap, p_scale=p_scale,
                                interpret=_interpret())
