"""Pallas TPU kernel: fused token + segment + position embedding.

The paper's Tensor-fusion contribution (§3.1): BERT's three embedding
lookups run as three CUDA kernels in FasterTransformer; SAMP fuses them into
one. TPU translation (DESIGN.md §2): three HBM gathers + two adds + scale in
one kernel. The tables stay in HBM in their own layout
(``memory_space=ANY``); the token / segment / position ids are
scalar-prefetched into SMEM, and each grid step gathers ``bt`` rows from
each table and writes one fused ``(bt, D)`` output block. One pass over HBM
instead of three.

A single table row is not a legal TPU block or DMA slice: HBM arrays are
tiled in groups of ``rt`` rows (8 for 32-bit, 16 for 16-bit dtypes) and
Mosaic only slices whole tile rows. So each gathered row arrives as the
aligned ``(rt, D)`` tile-row group that holds it (one DMA), and the kernel
selects the wanted row in VMEM with a mask-and-sum over the group's rows.
Tables whose row count is not a multiple of ``rt`` are padded (the segment
table's 2 rows, odd vocabularies).

Position ids are an explicit prefetch operand so callers with non-trivial
position streams (the serving runtime's pad-masked positions, packed
sequences) fuse correctly; ``positions=None`` falls back to the row-major
``arange(N) mod S`` convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: output rows per grid step (a multiple of every sublane tile height)
BLOCK_ROWS = 32


def _row_tile(dtype) -> int:
    """Rows in one HBM tile row group: 8 for 32-bit, 16 for 16-bit."""
    return 32 // jnp.dtype(dtype).itemsize


def _kernel(tok_ids, seg_ids, pos_ids, tok_hbm, pos_hbm, seg_hbm, o_ref,
            tok_buf, pos_buf, seg_buf, sem, *, scale: float, bt: int):
    base = pl.program_id(0) * bt
    tables = ((tok_ids, tok_hbm, tok_buf), (pos_ids, pos_hbm, pos_buf),
              (seg_ids, seg_hbm, seg_buf))

    def copies(r):
        out = []
        for t, (ids, hbm, buf) in enumerate(tables):
            rt = buf.shape[1]
            start = pl.multiple_of((ids[base + r] // rt) * rt, rt)
            out.append(pltpu.make_async_copy(hbm.at[pl.ds(start, rt)],
                                             buf.at[r], sem.at[t]))
        return out

    @pl.loop(0, bt)
    def _start(r):
        for c in copies(r):
            c.start()

    @pl.loop(0, bt)
    def _wait(r):
        for c in copies(r):
            c.wait()

    for r in range(bt):
        row = None
        for t, (ids, _, buf) in enumerate(tables):
            rt = buf.shape[1]
            group = buf[r].astype(jnp.float32)                 # (rt, D)
            pick = jax.lax.broadcasted_iota(jnp.int32, group.shape, 0) \
                == ids[base + r] % rt
            x = jnp.sum(jnp.where(pick, group, 0.0), axis=0, keepdims=True)
            if t == 0 and scale != 1.0:
                x = x * scale
            row = x if row is None else row + x
        o_ref[pl.ds(r, 1), :] = row.astype(o_ref.dtype)


def fused_embed(tokens: jax.Array, tok_table: jax.Array,
                pos_table: jax.Array, seg_table: jax.Array | None,
                segments: jax.Array | None, *,
                positions: jax.Array | None = None, scale: float = 1.0,
                out_dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    """tokens: (N,) int32 (flattened batch*seq); tables: (V|P|S, D).
    ``positions``: (N,) int32 rows into ``pos_table``; when None the rows
    are ``arange(N) mod pos_table.shape[0]`` — the caller flattens (B, S)
    row-major so position ids repeat per sequence. Returns (N, D).
    """
    N = tokens.shape[0]
    D = tok_table.shape[1]
    if seg_table is None:
        seg_table = jnp.zeros((1, D), tok_table.dtype)
        segments = jnp.zeros((N,), jnp.int32)
    S = pos_table.shape[0]
    if positions is None:
        positions = jnp.arange(N, dtype=jnp.int32) % S

    def pad_rows(t):
        rt = _row_tile(t.dtype)
        return t if t.shape[0] % rt == 0 else \
            jnp.pad(t, ((0, -t.shape[0] % rt), (0, 0)))
    tok_table, pos_table, seg_table = (pad_rows(t) for t in
                                       (tok_table, pos_table, seg_table))
    # the id streams are padded with row 0 up to a whole number of blocks;
    # the padding rows are sliced off the output
    bt = BLOCK_ROWS
    n_pad = -(-N // bt) * bt
    ids = [jnp.pad(a.astype(jnp.int32), (0, n_pad - N))
           for a in (tokens, segments, positions)]
    kernel = functools.partial(_kernel, scale=float(scale), bt=bt)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_pad // bt,),
        in_specs=[hbm, hbm, hbm],
        out_specs=pl.BlockSpec((bt, D), lambda i, tok, seg, pos: (i, 0)),
        scratch_shapes=[pltpu.VMEM((bt, _row_tile(t.dtype), D), t.dtype)
                        for t in (tok_table, pos_table, seg_table)]
        + [pltpu.SemaphoreType.DMA((3,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, D), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*ids, tok_table, pos_table, seg_table)
    return out[:N]
