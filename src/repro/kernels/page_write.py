"""In-place page write for the int8 paged KV pool.

Each decode tick appends one token per slot: a new K row and V row per
KV head (int8) and, under per-token scales, one float32 scale per head.
The pool is head-major, ``(L, NP, Hkv, ps, hd)`` stacked over the layers
of a scan group (see :mod:`repro.kernels.decode_attention`), and this
kernel writes each slot's row into its page of one layer **in place**:
the pool operands are aliased to the outputs (``input_output_aliases``),
so the pool stays in the row-major tiled layout the decode kernel reads
and XLA has no scatter to relayout it for.

A single int8 row sits inside a packed tile, which a DMA cannot address,
so the kernel moves whole pages ``(Hkv, ps, ...)``. The grid walks the
slots; grid step ``b`` holds slot ``b``'s page as its block (the page id
is scalar-prefetched into the index map, so the pipeline fetches step
``b + 1``'s page while step ``b`` runs), replaces the slot's row with a
masked select and writes the page back.

A slot whose page id is ``-1`` (inactive, an unallocated table entry, a
position past the table) writes nothing, as the XLA scatter's
``mode="drop"`` does: its grid step keeps the previous slot's block, so
the pipeline neither fetches nor writes back a page for it, and its row
matches no row. Two slots never write one page in the same call: every
page belongs to one slot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _page_write_kernel(layer_ref, page_ref, row_ref, *refs, pools: int):
    rows, pages = refs[:pools], refs[pools:2 * pools]
    outs = refs[2 * pools:]              # aliased to ``pages``
    b = pl.program_id(0)
    # the output block stays resident while the page id repeats; a new id
    # starts from the page as it is in the pool
    fresh = jnp.logical_or(b == 0,
                           page_ref[b] != page_ref[jnp.maximum(b - 1, 0)])
    for new, page, out in zip(rows, pages, outs):
        @pl.when(fresh)
        def _load(page=page, out=out):
            out[...] = page[...]

        old = out[...]                   # (1, 1, Hkv, ps, ...)
        at = jax.lax.broadcasted_iota(jnp.int32, old.shape, 3)
        out[...] = jnp.where(at == row_ref[b], new[...], old)


def page_write(pools, rows, layer, page, row, *, interpret: bool = False):
    """Write one row per slot into its page of layer ``layer``, in place.

    Args:
      pools: a tuple of pool leaves ``(L, NP, Hkv, ps, *tail)`` — int8 K/V
        pages (``tail=(hd,)``) and their float32 per-token scale pages
        (no tail) — updated in place.
      rows: per pool, the slots' new rows ``(B, Hkv, *tail)`` in the
        pool's dtype, zero-padded here to a lane-padded pool's width; a
        scale pool's rows ``(B, Hkv)``.
      layer: int32 scalar, the layer of the stack to write.
      page: ``(B,)`` int32 page id per slot, ``-1`` to write nothing.
      row: ``(B,)`` int32 row within the page.

    Returns the updated pools, in the order given.
    """
    n, B = len(pools), page.shape[0]
    for p, r in zip(pools, rows):
        if r.shape[:2] != (B, p.shape[2]) or r.ndim != p.ndim - 2 \
                or r.dtype != p.dtype:
            raise ValueError(f"rows {r.shape} {r.dtype} do not fit pool "
                             f"{p.shape} {p.dtype} over {B} slots")
    page = page.astype(jnp.int32)
    live = page >= 0
    # a dropped slot's step keeps the block of the live slot before it (or,
    # before the first live slot, that slot's), so no page moves for it
    slot = jnp.arange(B, dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(live, slot, -1))
    prev = jnp.where(prev < 0, jnp.argmax(live).astype(jnp.int32), prev)
    block = jnp.maximum(page[prev], 0)
    row = jnp.where(live, row.astype(jnp.int32), -1)
    # each slot's row broadcast over its page (and zero-padded to a K/V
    # page's lanes): the select picks one row, the page's axis 3
    wide = [jnp.broadcast_to(
        jnp.pad(r, [(0, 0)] * (r.ndim - 1)
                + [(0, p.shape[-1] - r.shape[-1])])[:, None, :, None]
        if r.ndim == 3 else r[:, None, :, None],
        (B, 1) + p.shape[2:]) for p, r in zip(pools, rows)]

    def pool_spec(p):
        return pl.BlockSpec((1, 1) + p.shape[2:],
                            lambda b, ly, pg, rw: (ly[0], pg[b])
                            + (0,) * (p.ndim - 2))

    def row_spec(r):
        return pl.BlockSpec((1,) + r.shape[1:],
                            lambda b, ly, pg, rw: (b,) + (0,) * (r.ndim - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[row_spec(r) for r in wide] + [pool_spec(p) for p in pools],
        out_specs=[pool_spec(p) for p in pools],
    )
    out = pl.pallas_call(
        functools.partial(_page_write_kernel, pools=n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands: layer, block, row, the rows, then the pools
        input_output_aliases={3 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), block, row, *wide, *pools)
    return tuple(out)
