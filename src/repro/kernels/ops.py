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
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels import addnorm_quant as _anq
from repro.kernels import decode_attention as _da
from repro.kernels import dynamic_quant as _dq
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_embed as _fe
from repro.kernels import page_write as _pw
from repro.kernels import quant_linear as _ql


def _interpret() -> bool:
    """Interpret mode everywhere but a TPU, where Mosaic compiles the
    kernels. Called at trace time, never at import."""
    return jax.default_backend() != "tpu"


#: a TPU vector register's lanes: the minor dim of a row-major tile
LANES = 128


def page_lanes() -> int:
    """The lane width a KV page leaf's minor dim is padded to for the
    kernels that read it: whole TPU lanes where Mosaic compiles them (so
    XLA's compact layout of the pool is the row-major one they read), 1 in
    interpret mode."""
    return 1 if _interpret() else LANES


def lane_tiled(n: int) -> bool:
    """Whether a GEMM dim of ``n`` splits into the whole 128-lane blocks
    Mosaic tiles (or fits one block): always in interpret mode. A dense
    FFN 10944 wide (deepseek-v2-lite) does not."""
    return _interpret() or n <= LANES or n % LANES == 0


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


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def quant_expert_gemm(xe, w_q, w_scale, xs=None, *, out_dtype=jnp.float32):
    """Grouped per-expert W8A8 GEMM: a routed buffer ``xe (..., E, C, D)``
    against an int8 expert stack ``w_q (E, D, F)`` -> ``(..., E, C, F)``,
    in ONE Pallas kernel (``quant_linear.quant_expert_gemm``; the trace
    names it ``quant_expert_gemm``) whose grid walks the experts.

    Per-expert scales are **operands**: ``w_scale`` broadcastable to
    (E, 1, F) (per-expert-per-channel, the v4 ``experts`` family layout) and
    ``xs`` broadcastable to (E, 1, 1) (per-expert static activation scales;
    a scalar is one scale for every expert; ``None`` selects per-token
    dynamic quantization).
    """
    from repro.core.quantize import quantize, quantize_per_token
    E, D, F = w_q.shape
    lead = xe.shape[:-3]
    ws = jnp.asarray(w_scale, jnp.float32)
    ws = jnp.broadcast_to(ws.reshape((1, 1, -1) if ws.ndim < 3 else ws.shape),
                          (E, 1, F)).reshape(E, F)
    # Quantize the whole routed buffer in ONE op, exactly the subgraph the
    # reference einsum path builds: a different fusion of the round
    # (reciprocal-multiply vs divide) can flip a code at a rounding
    # boundary, an O(scale) output step the router then amplifies into a
    # different top-k choice. Identical subgraph -> identical codes ->
    # backend choice never moves the routing.
    if xs is not None:
        xs_b = jnp.asarray(xs, jnp.float32)
        xs_b = xs_b if xs_b.ndim == 0 else xs_b.reshape(-1, 1, 1)
        codes = quantize(xe, xs_b)
        rows_scale = jnp.broadcast_to(xs_b, xe.shape[:-1] + (1,))
    else:
        xq = quantize_per_token(xe)                      # (..., E, C, 1)
        codes, rows_scale = xq.values, xq.scale
    # (G, E, C, .) -> (E, G * C, .): each expert's rows of every group
    G = math.prod(lead)
    C = xe.shape[-2]

    def by_expert(a):
        a = a.reshape((G, E, C, a.shape[-1]))
        return a.transpose(1, 0, 2, 3).reshape(E, G * C, a.shape[-1])
    y = _ql.quant_expert_gemm(by_expert(codes), w_q, ws,
                              by_expert(rows_scale), out_dtype=out_dtype,
                              interpret=_interpret())
    y = y.reshape(E, G, C, F).transpose(1, 0, 2, 3)
    return y.reshape(lead + (E, C, F))


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
                     p_scale=None, layer=None):
    """Paged int8-KV decode attention (single query token per slot).

    ``page_table``/``lengths`` are operands — slots churn every step and
    must not retrace; the kv scheme (``per_head``) and page geometry are
    static and baked into the executable key by the serving runtime.
    ``p_scale`` (the plan's ``softmax='uint8'`` scheme) is a scalar
    operand; its presence selects the two-pass quantized-softmax grid.
    ``layer`` (an operand) picks the layer of a pool stacked over a scan
    group's layers."""
    return _da.decode_attention(q, k_pages, v_pages, page_table, lengths,
                                k_scale=k_scale, v_scale=v_scale,
                                per_head=per_head, scale=scale,
                                softcap=softcap, p_scale=p_scale,
                                layer=layer, interpret=_interpret())


@jax.jit
def page_write(pools, rows, layer, page, row):
    """Write each slot's new row into its page of layer ``layer`` of the
    stacked int8 pools (K/V pages and their per-token scale pages), in
    place; ``page`` -1 drops a slot's write. All operands are arrays: the
    slots, their pages and the layer change every call without
    retracing."""
    return _pw.page_write(tuple(pools), tuple(rows), layer, page, row,
                          interpret=_interpret())
