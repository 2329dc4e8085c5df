"""Paged decode attention with fused int8-KV dequantization.

Single-token decode attention over a **paged** KV cache: keys/values live in
a shared pool of fixed-size, head-major pages ``(num_pages, Hkv, page_size,
hd)`` and each slot owns an ordered list of page ids (its *page table* row, ``-1`` for
unallocated entries). The pool may come stacked over the layers of a scan
group, ``(L, num_pages, ...)``, with the layer to read as a scalar-prefetch
operand. Its minor dims may be padded to whole TPU lanes (head_dim for
K/V, the page's tokens for the scales; see ``layers.page_leaf_shape``), so
that its compact device layout is the row-major one a page is read in:
the query is then zero-padded to the K/V width and the output cut back to
head_dim.

The pools stay in HBM (``memory_space=pl.ANY``) and the kernel fetches a
slot's pages itself. One grid step serves one slot: it walks the slot's
*live* pages, the first ``ceil(lengths[b] / page_size)`` entries of its
table row, in **blocks** of ``block_pages`` pages (enough pages for a
128-lane row of scores, at most the whole row). A block arrives as one
batch of concurrent DMAs, one per page of every KV head (and one per page
of per-token scales), into one of two VMEM buffers; the next block's DMAs
start before the current block is computed, and the last block of a slot
starts the first block of the next slot with live pages, so the copies
run under the compute across grid steps as well. Blocks past a slot's
length start no DMA and run no compute: a slot's cost follows its length,
not the table's width, and a slot of length 0 is one empty step that
writes zeros. A ``-1`` entry inside the live range is never fetched and
its tokens are masked, so freed pages never leak stale tokens into
another slot even before they are rewritten.

K/V pages are int8.  Dequantization is fused into the two matmul epilogues
rather than materializing a float cache:

* QK^T epilogue — raw scores ``q @ k_i8^T`` over a block's tokens are
  scaled by the key scale (the block's per-token row, gathered from its
  scale pages, or a per-head scalar from the calibrated vector).
* PV epilogue — softmax probabilities are scaled by the value scale before
  the ``p @ v_i8`` dot, which is algebraically ``p @ (v_i8 * s)``.

Softmax is the standard online (flash) recurrence across a slot's blocks
with ``(g, 1)`` running max/denominator per KV head, where
``g = Hq // Hkv`` is the GQA group: queries arrive as ``(B, Hkv, g, hd)``
so each head's QK^T is one ``(g, block_pages * page_size)`` tile against
that head's rows of the block, the heads taken in turn.

Masking is positional: token ``t`` of slot ``b`` is visible iff
``t < lengths[b]`` and its page's table entry is not ``-1``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: tokens a page block should cover: one 128-lane row of scores per query
BLOCK_TOKENS = 128


def block_pages(page_size: int, pages_per_slot: int) -> int:
    """Pages a block of the walk holds: as many as cover ``BLOCK_TOKENS``
    tokens, at most a slot's whole table row."""
    return min(pages_per_slot, -(-BLOCK_TOKENS // page_size))


def _decode_kernel(ly_ref, pt_ref, len_ref, q_ref, k_hbm, v_hbm, ks_ref,
                   vs_ref, ps_ref, o_ref, k_buf, v_buf, ks_buf, vs_buf,
                   sems, base_ref, *, page_size: int, head_dim: int,
                   pages_per_slot: int, block: int, scale: float,
                   softcap: Optional[float], per_head: bool, quant_p: bool):
    b, slots = pl.program_id(0), pl.num_programs(0)
    ps, pps = page_size, pages_per_slot
    heads = q_ref.shape[1]
    width = block * ps
    # quant_p walks a slot's blocks twice: pass 1 accumulates the exact
    # global softmax max/denominator, pass 2 revisits every block with the
    # *normalized* probabilities in hand, quantizes them with the unsigned
    # uint8 scheme at the calibrated softmax scale, and accumulates the
    # already-normalized P·V — the quantized-softmax epilogue cannot ride
    # the single-pass online recurrence because the codes are defined on
    # final probabilities, not running partials.
    passes = 2 if quant_p else 1

    def live_pages(s):
        return jnp.minimum(jax.lax.div(len_ref[s] + ps - 1, ps), pps)

    def blocks(s):
        return jax.lax.div(live_pages(s) + block - 1, block)

    def pages_of(s, visit):
        """(fetched?, page id) of each page of slot ``s``'s visit
        ``visit``; a visit is one pass's walk over one block."""
        blk = jax.lax.rem(visit, blocks(s)) if quant_p else visit
        out = []
        for p in range(block):
            j = blk * block + p
            entry = pt_ref[s * pps + jnp.minimum(j, pps - 1)]
            out.append((jnp.logical_and(j < live_pages(s), entry >= 0),
                        jnp.maximum(entry, 0)))
        return out

    def copies(page, p, buf):
        srcs = [(k_hbm, k_buf), (v_hbm, v_buf)]
        if not per_head:
            srcs += [(ks_ref, ks_buf), (vs_ref, vs_buf)]
        return [pltpu.make_async_copy(src.at[ly_ref[0], page],
                                      dst.at[buf, p], sems.at[i, buf])
                for i, (src, dst) in enumerate(srcs)]

    def fetch(s, visit, buf, wait: bool):
        # a page's wait is guarded by the very condition its start was,
        # read from the same prefetched table and lengths
        for p, (fetched, page) in enumerate(pages_of(s, visit)):
            @pl.when(fetched)
            def _():
                for c in copies(page, p, buf):
                    c.wait() if wait else c.start()

    visits = passes * blocks(b)
    nxt = jnp.minimum(b + 1, slots - 1)
    has_next = jnp.logical_and(b + 1 < slots, blocks(nxt) > 0)

    @pl.when(b == 0)
    def _first():
        base_ref[0] = 0

        @pl.when(visits > 0)
        def _():
            fetch(0, 0, 0, wait=False)

    base = base_ref[0]

    @pl.when(jnp.logical_and(visits == 0, has_next))
    def _hand_on():                      # an empty slot starts the next one
        fetch(nxt, 0, base, wait=False)

    def scale_row(ref, buf, h):
        # head h's dequantization scale: an SMEM scalar (per-head), or the
        # block's per-token row, page by page from its scale pages
        if per_head:
            return ref[h]
        return jnp.concatenate([ref[buf, p, h:h + 1, :ps]
                                for p in range(block)], axis=1)

    def rows(ref, buf, h):               # head h's (block * ps, lanes) rows
        return jnp.concatenate([ref[buf, p, h].astype(jnp.float32)
                                for p in range(block)], axis=0)

    k_scale, v_scale = (ks_ref, vs_ref) if per_head else (ks_buf, vs_buf)

    def scores(h, buf, valid):
        q = q_ref[0, h].astype(jnp.float32) * scale          # (g, lanes)
        s = jax.lax.dot_general(q, rows(k_buf, buf, h),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # QK^T epilogue: dequantize raw int8 scores by the key scale.
        s = s * scale_row(k_scale, buf, h)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        return jnp.where(valid, s, NEG_INF)

    def pv(h, buf, valid, p):
        # PV epilogue: fold the value scale into p, then one int8-V dot;
        # the select keeps unfetched rows' stale scales out of the sum
        p = jnp.where(valid, p * scale_row(v_scale, buf, h), 0.0)
        return jax.lax.dot_general(p, rows(v_buf, buf, h),
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def body(visit, carry):
        m, l, acc = carry
        buf = jax.lax.rem(base + visit, 2)

        @pl.when(visit + 1 < visits)
        def _():
            fetch(b, visit + 1, 1 - buf, wait=False)

        @pl.when(jnp.logical_and(visit + 1 == visits, has_next))
        def _():
            fetch(nxt, 0, 1 - buf, wait=False)

        fetch(b, visit, buf, wait=True)
        blk = jax.lax.rem(visit, blocks(b)) if quant_p else visit
        tok = blk * width + jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        valid = tok < len_ref[b]
        lane = tok - blk * width
        for p, (fetched, _) in enumerate(pages_of(b, visit)):
            in_page = jnp.logical_and(lane >= p * ps, lane < (p + 1) * ps)
            valid = jnp.logical_and(
                valid, jnp.logical_or(fetched, jnp.logical_not(in_page)))
        m, l, acc = list(m), list(l), list(acc)
        for h in range(heads):
            s = scores(h, buf, valid)

            def online(m_h, l_h, acc_h, with_acc, s=s, h=h):
                m_new = jnp.maximum(m_h, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_h - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)     # (g, T)
                l_h = l_h * alpha + jnp.sum(p, axis=1, keepdims=True)
                if with_acc:
                    acc_h = acc_h * alpha + pv(h, buf, valid, p)
                return m_new, l_h, acc_h

            def second(m_h, l_h, acc_h, s=s, h=h):
                # normalized probabilities -> uint8 codes -> dequantized P·V
                p = jnp.exp(s - m_h) / jnp.maximum(l_h, 1e-30)
                pq = jnp.clip(jnp.round(p / ps_ref[...]), 0, 255)
                return m_h, l_h, acc_h + pv(h, buf, valid, pq * ps_ref[...])

            if quant_p:
                m[h], l[h], acc[h] = jax.lax.cond(
                    visit < visits // 2,
                    functools.partial(online, with_acc=False), second,
                    m[h], l[h], acc[h])
            else:
                m[h], l[h], acc[h] = online(m[h], l[h], acc[h], True)
        return tuple(m), tuple(l), tuple(acc)

    g, lanes = q_ref.shape[2:]
    init = (tuple(jnp.full((g, 1), NEG_INF, jnp.float32)
                  for _ in range(heads)),
            tuple(jnp.zeros((g, 1), jnp.float32) for _ in range(heads)),
            tuple(jnp.zeros((g, lanes), jnp.float32) for _ in range(heads)))
    _, l, acc = jax.lax.fori_loop(0, visits, body, init)
    base_ref[0] = jax.lax.rem(base + visits, 2)
    for h in range(heads):
        out = acc[h][:, :head_dim]
        if not quant_p:                  # pass 2 accumulates pre-normalized
            out = out / jnp.maximum(l[h], 1e-30)
        o_ref[0, h] = out.astype(o_ref.dtype)


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
        blocks — quantized codes are defined on *final* probabilities, so
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
    block = block_pages(page_size, pps)
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

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if per_head:
        scale_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        scale_buf = pltpu.SMEM((1,), jnp.float32)     # unused: no DMA
    else:
        scale_spec = hbm
        scale_buf = pltpu.VMEM((2, block, Hkv, k_scale.shape[-1]),
                               jnp.float32)
    page_buf = pltpu.VMEM((2, block, Hkv, page_size, width), jnp.int8)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, width),
                         lambda bi, ly, pt, ln: (bi, 0, 0, 0)),
            hbm,
            hbm,
            scale_spec,
            scale_spec,
            pl.BlockSpec((1, 1), lambda bi, ly, pt, ln: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, hd),
                               lambda bi, ly, pt, ln: (bi, 0, 0, 0)),
        scratch_shapes=[
            page_buf, page_buf, scale_buf, scale_buf,
            # one DMA semaphore per operand (K, V, K scales, V scales) and
            # buffer; the buffer the next slot's first block lands in
            pltpu.SemaphoreType.DMA((4, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, head_dim=hd,
        pages_per_slot=pps, block=block,
        scale=scale, softcap=softcap, per_head=per_head, quant_p=quant_p)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        # the walk hands a slot's successor its first block: one ordered
        # sweep over the slots
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(ly, pt_flat, lengths, q, k_pages, v_pages, k_scale, v_scale, ps_op)
