"""Paged decode attention with fused int8-KV dequantization.

Single-token decode attention over a **paged** KV cache: keys/values live in
a shared pool of fixed-size, head-major pages ``(num_pages, Hkv, page_size,
hd)`` and each slot owns an ordered list of page ids (its *page table* row, ``-1`` for
unallocated entries). The pool may come stacked over the layers of a scan
group, ``(L, num_pages, ...)``, with the layer to read as a scalar-prefetch
operand. Its minor dims may be padded to whole TPU lanes (head_dim for
K/V, the page's tokens for the scales; see ``layers.page_leaf_shape``), so
that its compact device layout is the row-major one a block is read in:
the query is then zero-padded to the K/V width and the output cut back to
head_dim.  The kernel walks a slot's page table with the page
axis as the innermost grid dimension, using **scalar prefetch** so the page
id for grid step ``j`` indexes the pool *in the BlockSpec index map* — the
DMA engine fetches exactly the pages a slot owns, never the whole pool.

The pool is head-major so one grid step's block, one page of every KV
head ``(1, 1, Hkv, page_size, hd)``, spans the array's whole last two dims:
Mosaic accepts a block only when its last two dims are tile multiples or
the full dims, and a token-major ``(1, page_size, 1, hd)`` block over
``(.., Hkv, hd)`` is neither once ``Hkv > 1``. For the same reason a page's
per-token scales arrive as one ``(Hkv, page_size)`` block and the kernel
picks each head's row, and per-head scales sit in SMEM. A grid step per
page, not per page and head, halves the DMAs, and read from HBM the
kernel's time goes with their number.

K/V pages are int8.  Dequantization is fused into the two matmul epilogues
rather than materializing a float cache:

* QK^T epilogue — raw scores ``q @ k_i8^T`` are scaled by the key scale
  (a per-token ``(page_size,)`` row gathered from the scale pages, or a
  per-head scalar from the calibrated vector).
* PV epilogue — softmax probabilities are scaled by the value scale before
  the ``p @ v_i8`` dot, which is algebraically ``p @ (v_i8 * s)``.

Softmax is the standard online (flash) recurrence across pages with
``(g, 1)`` running max/denominator scratch per KV head, where
``g = Hq // Hkv`` is the GQA group: queries arrive as ``(B, Hkv, g, hd)``
so each head's QK^T in a grid step is a ``(g, page_size)`` tile against
that head's page, the heads taken in turn.

Masking is positional: token ``t = j * page_size + lane`` of slot ``b`` is
visible iff ``t < lengths[b]``.  Pages the slot does not own (table entry
``-1``) are skipped entirely via ``pl.when``; freed pages therefore never
leak stale tokens into another slot even before they are rewritten.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _decode_kernel(ly_ref, pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                   vs_ref, ps_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   page_size: int, head_dim: int, pages_per_slot: int,
                   scale: float,
                   softcap: Optional[float], per_head: bool, quant_p: bool):
    b, j = pl.program_id(0), pl.program_id(1)
    # quant_p doubles the page axis: pass 1 (j < pps) accumulates the exact
    # global softmax max/denominator, pass 2 (j >= pps) revisits every page
    # with the *normalized* probabilities in hand, quantizes them with the
    # unsigned uint8 scheme at the calibrated softmax scale, and
    # accumulates the already-normalized P·V — the quantized-softmax
    # epilogue cannot ride the single-pass online recurrence because the
    # codes are defined on final probabilities, not running partials.
    jj = jax.lax.rem(j, pages_per_slot) if quant_p else j
    heads = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _scale_row(ref, h):
        # head h's dequantization scale: an SMEM scalar (per-head), or row
        # h of the page's (Hkv, ps) per-token scale block
        if per_head:
            return ref[h]
        sc = ref[0, 0]
        pick = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) == h
        row = jnp.sum(jnp.where(pick, sc, 0.0), axis=0, keepdims=True)
        return row[:, :page_size]                # cut a lane-padded page

    length = len_ref[b]
    page = pt_ref[b * pages_per_slot + jj]
    live = jnp.logical_and(page >= 0, length > jj * page_size)

    def _scores(h):
        q = q_ref[0, h].astype(jnp.float32) * scale          # (g, width)
        k = k_ref[0, 0, h].astype(jnp.float32)               # (ps, width)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # QK^T epilogue: dequantize raw int8 scores by the key scale.
        s = s * _scale_row(ks_ref, h)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        tok = jj * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        return jnp.where(tok < length, s, NEG_INF)

    def _pv(h, p):
        # PV epilogue: fold the value scale into p, then one int8-V dot.
        p = p * _scale_row(vs_ref, h)
        v = v_ref[0, 0, h].astype(jnp.float32)               # (ps, width)
        return jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def _stats_update(h, s, with_acc: bool):
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # (g, ps)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[h] = m_new
        if with_acc:
            acc_ref[h] = acc_ref[h] * alpha + _pv(h, p)

    if not quant_p:
        @pl.when(live)
        def _body():
            for h in range(heads):
                _stats_update(h, _scores(h), with_acc=True)

        @pl.when(j == pages_per_slot - 1)
        def _finish():
            for h in range(heads):
                o_ref[0, h] = (acc_ref[h][:, :head_dim]
                               / jnp.maximum(l_ref[h], 1e-30)
                               ).astype(o_ref.dtype)
    else:
        @pl.when(jnp.logical_and(live, j < pages_per_slot))
        def _pass1():
            for h in range(heads):
                _stats_update(h, _scores(h), with_acc=False)

        @pl.when(jnp.logical_and(live, j >= pages_per_slot))
        def _pass2():
            # normalized probabilities -> uint8 codes -> dequantized P·V
            for h in range(heads):
                p = jnp.exp(_scores(h) - m_ref[h]) \
                    / jnp.maximum(l_ref[h], 1e-30)
                pq = jnp.clip(jnp.round(p / ps_ref[...]), 0, 255)
                acc_ref[h] += _pv(h, pq * ps_ref[...])

        @pl.when(j == 2 * pages_per_slot - 1)
        def _finish_q():
            for h in range(heads):                       # pre-normalized
                o_ref[0, h] = acc_ref[h][:, :head_dim].astype(o_ref.dtype)


def decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                     k_scale, v_scale, per_head: bool,
                     scale: Optional[float] = None,
                     softcap: Optional[float] = None,
                     p_scale=None, layer=None,
                     interpret: bool = False):
    """Paged int8-KV decode attention.

    Args:
      q: ``(B, Hkv, g, hd)`` float queries, GQA groups pre-folded
        (query head ``h*g + i`` shares KV head ``h``).
      k_pages / v_pages: ``(num_pages, Hkv, page_size, hd_pool)`` int8
        pool, or ``(L, num_pages, Hkv, page_size, hd_pool)`` stacked over
        layers; ``hd_pool >= hd``, zero past ``hd`` (lane padding).
      page_table: ``(B, pages_per_slot)`` int32, ``-1`` = unallocated.
      lengths: ``(B,)`` int32 — valid tokens per slot **including** the
        token written this step; 0 disables a slot (output row is zeros).
      k_scale / v_scale: per-token ``(num_pages, Hkv, >= page_size)``
        float32 scale pages (stacked ``(L, ...)`` as the pool is) when
        ``per_head=False``; calibrated ``(Hkv,)`` float32 vectors when
        ``per_head=True``.
      scale: query scaling, default ``hd**-0.5``.
      softcap: optional tanh soft-capping of logits.
      p_scale: the layer's calibrated softmax scale (``amax/255``; a scalar
        operand). When given, softmax probabilities are quantized to
        unsigned-int8 codes in the PV epilogue (the plan's
        ``softmax='uint8'`` scheme) via a second pass over the slot's
        pages — quantized codes are defined on *final* probabilities, so
        the single-pass online recurrence cannot carry them.
      layer: int32 scalar, the layer of a stacked pool to read (default 0).

    Returns ``(B, Hkv, g, hd)`` in ``q.dtype``.
    """
    B, Hkv, g, hd = q.shape
    if k_pages.ndim == 4:                  # one layer's pool: a stack of one
        k_pages, v_pages = k_pages[None], v_pages[None]
        if not per_head:
            k_scale, v_scale = k_scale[None], v_scale[None]
    page_size, width = k_pages.shape[3:]
    pps = page_table.shape[1]
    if scale is None:
        scale = float(hd) ** -0.5
    quant_p = p_scale is not None

    ly = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    if width > hd:                       # lane-padded pages: pad q with 0
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, width - hd)])
    pt_flat = page_table.reshape(-1).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    ps_op = jnp.asarray(p_scale if quant_p else 1.0,
                        jnp.float32).reshape(1, 1)

    # Scalar-prefetch args (ly, pt, ln) are appended to every index map; a -1
    # table entry is clamped to page 0 for the DMA and skipped in-kernel.
    # Under quant_p the page axis runs twice, so index maps fold j mod pps.
    def jmod(j):
        return jax.lax.rem(j, pps) if quant_p else j

    def page(bi, j, pt):
        return jnp.maximum(pt[bi * pps + jmod(j)], 0)


    if per_head:
        scale_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    else:
        scale_spec = pl.BlockSpec(
            (1, 1, Hkv, k_scale.shape[-1]),
            lambda bi, j, ly, pt, ln: (ly[0], page(bi, j, pt), 0, 0))
    page_spec = pl.BlockSpec(
        (1, 1, Hkv, page_size, width),
        lambda bi, j, ly, pt, ln: (ly[0], page(bi, j, pt), 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, 2 * pps if quant_p else pps),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, width),
                         lambda bi, j, ly, pt, ln: (bi, 0, 0, 0)),
            page_spec,
            page_spec,
            scale_spec,
            scale_spec,
            pl.BlockSpec((1, 1), lambda bi, j, ly, pt, ln: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, hd),
                               lambda bi, j, ly, pt, ln: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
            pltpu.VMEM((Hkv, g, width), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, head_dim=hd,
        pages_per_slot=pps,
        scale=scale, softcap=softcap, per_head=per_head, quant_p=quant_p)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ly, pt_flat, lengths, q, k_pages, v_pages, k_scale, v_scale, ps_op)
