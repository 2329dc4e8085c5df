"""The assigned input-shape grid + ShapeDtypeStruct input specs per cell.

Four shapes per LM architecture (40 cells total):

    train_4k      seq 4096,   global_batch 256   -> train_step
    prefill_32k   seq 32768,  global_batch 32    -> prefill (fwd + cache)
    decode_32k    seq 32768,  global_batch 128   -> serve_step (1 new token)
    long_500k     seq 524288, global_batch 1     -> serve_step, sub-quadratic
                                                    archs only

Skips (DESIGN.md §Arch-applicability): encoder-only archs (hubert) have no
decode; ``long_500k`` runs only where decode state is bounded (xlstm,
recurrentgemma, mixtral-SWA). ``input_specs`` returns weak-type-correct
ShapeDtypeStructs — nothing is allocated; the dry-run lowers against them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.plan import PrecisionPlan
from repro.models import transformer as T


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}


def cell_supported(cfg: ArchConfig, shape_name: str) -> tuple[bool, str]:
    """(supported, reason-if-not) per the DESIGN.md skip rules."""
    cell = SHAPES[shape_name]
    if cell.kind == "decode" and not cfg.supports_decode:
        return False, "encoder-only arch: no decode step"
    if shape_name == "long_500k" and not cfg.subquadratic:
        return False, "full-attention arch: 500k decode is not sub-quadratic"
    return True, ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ArchConfig, cell: ShapeCell,
                compute_dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStructs for the raw model inputs of one cell."""
    B = cell.global_batch
    S = cell.seq_len
    if cell.kind == "decode":
        return {"tokens": _sds((B, 1), jnp.int32)}
    if cfg.frontend == "audio":
        return {"frames": _sds((B, S, cfg.frontend_dim), compute_dtype),
                "labels": _sds((B, S), jnp.int32)}
    batch = {}
    if cfg.frontend == "vision":
        P = cfg.num_prefix_embeds
        batch["prefix_embeds"] = _sds((B, P, cfg.frontend_dim), compute_dtype)
        batch["tokens"] = _sds((B, S - P), jnp.int32)
    else:
        batch["tokens"] = _sds((B, S), jnp.int32)
    if cfg.family == "bert":
        batch["segments"] = _sds((B, S), jnp.int32)
        batch["labels"] = _sds((B,), jnp.int32)
    return batch


def cache_specs(cfg: ArchConfig, plan, cell: ShapeCell,
                cache_dtype=jnp.bfloat16):
    """Abstract decode caches (eval_shape over the real constructor)."""
    return jax.eval_shape(
        lambda: T.init_caches(cfg, plan, cell.global_batch,
                              cell.seq_len, cache_dtype))


def params_specs(cfg: ArchConfig, policy: PrecisionPlan,
                 param_dtype=jnp.bfloat16, head=None):
    return jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, policy,
                              head=head, dtype=param_dtype))


def prefill_step(params, batch, caches, cfg: ArchConfig, plan,
                 scheme: T.QuantScheme = T.QuantScheme(), *,
                 constrain=lambda x, _t: x, chunk=T.DEFAULT_CHUNK,
                 compute_dtype=jnp.bfloat16):
    """Serving prefill: full-sequence forward writing the KV caches, last-
    token logits only (what a real prefill returns). Encoder-only archs
    return the full per-frame logits and no cache."""
    if not cfg.supports_decode:
        logits, _ = T.forward(params, batch, cfg, plan, scheme,
                              constrain=constrain, chunk=chunk,
                              compute_dtype=compute_dtype)
        return logits, None
    hidden, new_caches = T.forward(
        params, batch, cfg, plan, scheme, caches=caches, pos=0,
        constrain=constrain, chunk=chunk, compute_dtype=compute_dtype,
        return_hidden=True)
    logits = constrain(T.unembed(hidden[:, -1:], params, cfg), "logits")
    return logits, new_caches
