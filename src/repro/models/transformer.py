"""Unified, config-driven model driver for every assigned architecture.

One code path executes all 11 families (dense / MoE / MLA / VLM / SSM /
audio / hybrid / BERT) by dispatching per-layer on :class:`BlockKind`, and
all SAMP precision policies by dispatching on the parameter leaf types
(float array vs QuantizedTensor — see repro.models.layers).

Execution plan (per-layer precision under ``lax.scan``)
-------------------------------------------------------
``lax.scan`` needs a homogeneous body, so the layer stack is split into
*groups*: maximal contiguous runs whose (BlockKind, LayerMode) sequence is
periodic with the arch's block pattern. Each group executes as one scan over
period-steps (params stacked on a leading ``steps`` axis); heterogeneous
leftovers unroll. A prefix-k policy on a homogeneous arch costs exactly two
scans — the paper's "configure the result to the toolkit" semantics, where
each (mode, k) candidate is its own compiled executable.

Observer capture (``obs`` != None) forces unrolled execution so per-layer
activation statistics escape the trace; capture is only used on
reduced/calibration-size models.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockKind
from repro.core.plan import LayerMode, PrecisionPlan
from repro.kernels.backend import ffn_input_scale
from repro.models import layers as L
from repro.models import rglru as R
from repro.models import xlstm as X

DEFAULT_CHUNK = 512          # query-block size for memory-efficient attention
Constrain = Callable[[jax.Array, str], jax.Array]
_IDENTITY: Constrain = lambda x, _tag: x


@dataclasses.dataclass(frozen=True)
class QuantScheme:
    """Numeric scheme knobs orthogonal to the per-layer policy lattice."""
    softmax_mode: str = "symmetric"   # paper default; 'unsigned' = our fix
    dynamic_acts: bool = False        # per-token activation quant (no xs)


@dataclasses.dataclass(frozen=True)
class Group:
    """One execution group: layers [start, stop), all in ``mode``, whose
    kind-sequence is ``kinds`` repeated ``steps`` times. ``quant_bmm``
    gates the attention score/value int8 matmuls: they follow the qkv
    block's spec, which can differ from the derived mode's
    ``quant_mha``."""
    start: int
    stop: int
    mode: LayerMode
    kinds: tuple[BlockKind, ...]
    steps: int
    quant_bmm: bool
    #: schema-v3 per-layer softmax dataflow scheme ('uint8' quantizes the
    #: softmax output between the score and value matmuls; None = follow
    #: the global QuantScheme.softmax_mode policy). Uniform within a group:
    #: PrecisionPlan.group_boundaries splits on full LayerPlan equality.
    softmax: Optional[str] = None

    @property
    def scan(self) -> bool:
        return self.steps >= 2


def build_plan(cfg: ArchConfig, precision: PrecisionPlan
               ) -> tuple[Group, ...]:
    """Execution plan for a :class:`~repro.core.plan.PrecisionPlan`: runs
    split on full per-block LayerPlan equality, so scan groups stay
    structurally homogeneous."""
    if precision.num_layers != cfg.num_layers:
        raise ValueError(f"plan has {precision.num_layers} layers, arch "
                         f"{cfg.num_layers}")
    kinds = cfg.layer_kinds()
    p = len(cfg.pattern)
    groups: list[Group] = []
    for (s, e, mode) in precision.group_boundaries():
        quant_bmm = precision.bmm_quantized(s)
        # 'float' layers follow the global QuantScheme softmax policy
        sm = precision.softmax_scheme(s)
        sm = None if sm == "float" else sm
        # Greedy maximal runs: prefer a homogeneous run; else a run that is
        # periodic with the arch's block pattern (possibly rotated); else a
        # single unrolled layer. Handles pattern alternation (gemma2,
        # recurrentgemma, xlstm) and aperiodic breaks (deepseek-v2's leading
        # dense-FFN layer) uniformly.
        i = s
        while i < e:
            j1 = i + 1
            while j1 < e and kinds[j1] == kinds[i]:
                j1 += 1
            jp = i
            if p > 1 and i + p <= e:
                period = tuple(kinds[i:i + p])
                jp = i + p
                while jp + p <= e and tuple(kinds[jp:jp + p]) == period:
                    jp += p
            if jp - i > max(j1 - i, p):
                groups.append(Group(i, jp, mode, tuple(kinds[i:i + p]),
                                    (jp - i) // p, quant_bmm, sm))
                i = jp
            else:
                groups.append(Group(i, j1, mode, (kinds[i],), j1 - i,
                                    quant_bmm, sm))
                i = j1
    return tuple(groups)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ArchConfig, kind: BlockKind,
               dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    if kind.body == "attn":
        attn = (L.init_mla(ks[0], cfg, dtype) if cfg.mla is not None
                else L.init_attention(ks[0], cfg, dtype))
        ffn = (L.init_moe(ks[1], cfg, dtype) if kind.moe
               else L.init_ffn(ks[1], cfg, dtype=dtype))
        return {"norm1": L.init_norm(cfg.norm_kind, cfg.d_model, dtype),
                "attn": attn,
                "norm2": L.init_norm(cfg.norm_kind, cfg.d_model, dtype),
                "ffn": ffn}
    if kind.body == "rglru":
        return {"norm1": L.init_norm(cfg.norm_kind, cfg.d_model, dtype),
                "rec": R.init_rglru(ks[0], cfg, dtype),
                "norm2": L.init_norm(cfg.norm_kind, cfg.d_model, dtype),
                "ffn": L.init_ffn(ks[1], cfg, dtype=dtype)}
    if kind.body == "mlstm":
        return {"norm1": L.init_norm(cfg.norm_kind, cfg.d_model, dtype),
                "blk": X.init_mlstm(ks[0], cfg, dtype)}
    if kind.body == "slstm":
        return {"norm1": L.init_norm(cfg.norm_kind, cfg.d_model, dtype),
                "blk": X.init_slstm(ks[0], cfg, dtype)}
    raise ValueError(f"unknown block body {kind.body!r}")


def _stack(trees: Sequence[Any]):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_params(key, cfg: ArchConfig, plan: Optional[PrecisionPlan] = None,
                *, head: Optional[tuple[str, int]] = None,
                dtype=jnp.float32) -> dict:
    """Float parameter init, packed per execution group. Quantized params are
    produced from these by repro.quant.ptq.apply_plan (PTQ: no re-training).
    """
    kemb, khead, klayers = jax.random.split(key, 3)
    params: dict = {"embed": L.init_embeddings(kemb, cfg, dtype)}
    lkeys = jax.random.split(klayers, cfg.num_layers)
    groups = []
    for g in build_plan(cfg,
                        plan or PrecisionPlan.full_float(cfg.num_layers)):
        period = []
        for j in range(len(g.kinds)):
            stack = [init_layer(lkeys[g.start + s * len(g.kinds) + j], cfg,
                                g.kinds[j], dtype)
                     for s in range(g.steps)]
            period.append(_stack(stack))
        groups.append({"layers": tuple(period)})
    params["groups"] = groups
    params["final_norm"] = L.init_norm(cfg.norm_kind, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(khead, cfg.d_model, cfg.vocab_size,
                                          False, dtype)
    if head is not None:
        kind, n_out = head
        kp, ko = jax.random.split(khead)
        if kind == "cls":     # CLS-pool classifier (classification/matching)
            params["head"] = {"pool": L.init_linear(kp, cfg.d_model,
                                                    cfg.d_model, True, dtype),
                              "out": L.init_linear(ko, cfg.d_model, n_out,
                                                   True, dtype)}
        elif kind == "ner":   # per-token tagger
            params["head"] = {"out": L.init_linear(ko, cfg.d_model, n_out,
                                                   True, dtype)}
        else:
            raise ValueError(f"unknown head kind {kind!r}")
    return params


def unpack_layers(params: dict, plan: tuple[Group, ...]) -> list:
    """Packed group params -> per-layer list (inverse of the init packing).
    Used by PTQ to requantize/repack under a different policy's plan."""
    layers = []
    for g, gp in zip(plan, params["groups"]):
        for s in range(g.steps):
            for j in range(len(g.kinds)):
                layers.append(jax.tree_util.tree_map(
                    lambda a, s=s: a[s], gp["layers"][j]))
    return layers


def pack_layers(layer_list: Sequence, plan: tuple[Group, ...]) -> list:
    """Per-layer list -> packed group params matching ``plan``."""
    groups = []
    for g in plan:
        period = []
        for j in range(len(g.kinds)):
            period.append(_stack(
                [layer_list[g.start + s * len(g.kinds) + j]
                 for s in range(g.steps)]))
        groups.append({"layers": tuple(period)})
    return groups


def repack(params: dict, old_plan: tuple[Group, ...],
           new_plan: tuple[Group, ...],
           transform=None) -> dict:
    """Repack ``params`` from ``old_plan``'s grouping to ``new_plan``'s,
    optionally applying ``transform(layer_idx, layer_params)`` per layer.
    The same as packing the transformed :func:`unpack_layers`, one stack
    of the new plan at a time: only that stack's layers are sliced out at
    once, so a model near the device's memory is not held twice over."""
    where = {}                  # layer index -> (group, period slot, step)
    for gi, g in enumerate(old_plan):
        for s in range(g.steps):
            for j in range(len(g.kinds)):
                where[g.start + s * len(g.kinds) + j] = (gi, j, s)

    def layer(i: int):
        gi, j, s = where[i]
        lp = jax.tree_util.tree_map(lambda a: a[s],
                                    params["groups"][gi]["layers"][j])
        return lp if transform is None else transform(i, lp)
    groups = []
    for g in new_plan:
        groups.append({"layers": tuple(
            _stack([layer(g.start + s * len(g.kinds) + j)
                    for s in range(g.steps)])
            for j in range(len(g.kinds)))})
    out = dict(params)
    out["groups"] = groups
    return out


# ---------------------------------------------------------------------------
# per-layer forward
# ---------------------------------------------------------------------------


def layer_forward(x, lp, cfg: ArchConfig, kind: BlockKind, mode: LayerMode,
                  scheme: QuantScheme, *, positions, obs, cache, chunk,
                  constrain: Constrain, quant_bmm: bool, active=None,
                  softmax=None, pages=None, backend=None):
    """One layer; returns ``(x, new_cache, routed)``, ``routed`` the picks
    its held experts computed (an int32 scalar; 0 outside MoE layers)."""
    quant = L.AttnQuant(enabled=quant_bmm,
                        softmax_mode=scheme.softmax_mode,
                        plan_scheme=softmax)
    spec = L.MaskSpec(
        causal=cfg.causal,
        window=cfg.sliding_window if kind.local else None,
        prefix_len=cfg.num_prefix_embeds if cfg.frontend == "vision" else 0)
    h = L.norm(x, lp["norm1"], cfg.norm_kind)
    new_cache = None
    routed = 0
    if kind.body == "attn":
        if cfg.mla is not None:
            a, new_cache = L.mla_block(
                h, lp["attn"], cfg, positions=positions, spec=spec,
                quant=quant, obs=obs, kv_cache=cache, active=active,
                chunk=chunk, pages=pages)
        else:
            a, new_cache = L.attention_block(
                h, lp["attn"], cfg, positions=positions, spec=spec,
                quant=quant, obs=obs, kv_cache=cache, active=active,
                constrain=constrain, chunk=chunk, pages=pages,
                backend=backend)
        if kind.moe:
            if isinstance(a, L.QuantActivation):
                a = a.dequantize()      # MoE residual keeps the float path
            x = constrain(x + a, "residual")
            h2 = L.norm(x, lp["norm2"], cfg.norm_kind)
            f, routed = L.moe_block(h2, lp["ffn"], cfg, obs=obs,
                                    constrain=constrain, backend=backend,
                                    active=active)
        else:
            # fused backends collapse add-residual + norm + requant into one
            # kernel when the ffn_in GEMM has a static int8 scale to feed
            ns = (ffn_input_scale(lp["ffn"], cfg.ffn_kind)
                  if backend is not None else None)
            x, h2 = L.residual_norm(a, x, lp["norm2"], cfg.norm_kind,
                                    next_scale=ns, backend=backend,
                                    constrain=constrain)
            f = L.ffn_block(h2, lp["ffn"], cfg, obs=obs, backend=backend)
        x = constrain(x + f, "residual")
    elif kind.body == "rglru":
        a, new_cache = R.rglru_mix(h, lp["rec"], cfg, obs=obs, state=cache,
                                   active=active)
        x = constrain(x + a, "residual")
        h2 = L.norm(x, lp["norm2"], cfg.norm_kind)
        x = constrain(x + L.ffn_block(h2, lp["ffn"], cfg, obs=obs,
                                      backend=backend),
                      "residual")
    else:
        blk = X.mlstm_block if kind.body == "mlstm" else X.slstm_block
        a, new_cache = blk(h, lp["blk"], cfg, obs=obs, state=cache,
                           active=active)
        x = constrain(x + a, "residual")
    return x, new_cache, routed


def run_groups(x, params, cfg: ArchConfig, plan: tuple[Group, ...],
               scheme: QuantScheme, *, positions, obs=None, caches=None,
               chunk=DEFAULT_CHUNK, constrain: Constrain = _IDENTITY,
               remat: bool = False, active=None, pages=None, backend=None):
    """Execute all layer groups. Returns (x, new_caches|None, routed):
    ``routed`` sums the picks the layers' held experts computed (an int32
    scalar, or 0 for a model without MoE layers).

    ``remat``: rematerialize each layer in the backward pass (activation
    checkpointing at layer-boundary granularity — the standard large-model
    memory policy: only the per-layer residual stream is saved).

    ``backend``: a ComputeBackend routing per-block ops to fused kernels;
    observer capture always runs the reference path (calibration observes
    the float dataflow the plan's scales were defined on).
    """
    if obs is not None:
        backend = None
    new_caches = [] if caches is not None else None
    routed = 0
    for gi, (g, gp) in enumerate(zip(plan, params["groups"])):
        gcache = caches[gi] if caches is not None else None
        unrolled = (obs is not None) or not g.scan

        def make_lf(kind, mode, lobs, g=g):
            def lf(xc, lp, lcache):
                return layer_forward(
                    xc, lp, cfg, kind, mode, scheme, positions=positions,
                    obs=lobs, cache=lcache, chunk=chunk, constrain=constrain,
                    active=active, quant_bmm=g.quant_bmm, softmax=g.softmax,
                    pages=pages, backend=backend)
            return (jax.checkpoint(lf) if remat and lobs is None else lf)

        if unrolled:
            ncs = []
            for s in range(g.steps):
                step_ncs = []
                for j, kind in enumerate(g.kinds):
                    idx = g.start + s * len(g.kinds) + j
                    lp = jax.tree_util.tree_map(lambda a, s=s: a[s],
                                                gp["layers"][j])
                    lcache = (None if gcache is None else
                              jax.tree_util.tree_map(lambda a, s=s: a[s],
                                                     gcache[j]))
                    if obs is not None:
                        lobs = ({"__values__": True}
                                if obs.get("__values__") else {})
                    else:
                        lobs = None
                    x, nc, r = make_lf(kind, g.mode, lobs)(x, lp, lcache)
                    routed = routed + r
                    if obs is not None:
                        for site, v in lobs.pop("__raw__", {}).items():
                            obs.setdefault("__raw__", {})[
                                f"layer{idx}/{site}"] = v
                        lobs.pop("__values__", None)
                        for site, v in lobs.items():
                            obs[f"layer{idx}/{site}"] = v
                    step_ncs.append(nc)
                ncs.append(tuple(step_ncs))
            if gcache is not None:
                # restack per period position: (steps, ...) leading axis
                new_caches.append(tuple(
                    _stack([ncs[s][j] for s in range(g.steps)])
                    for j in range(len(g.kinds))))
        else:
            # a decode step's int8 pools that the backend writes in place
            # ride the scan whole, as a carry, with the layer index (see
            # layers.POOL_KEYS): sliced per layer as xs/ys, every layer's
            # pool would be copied out and back on every step
            pools = tuple(
                {k: c[k] for k in L.POOL_KEYS if k in c}
                if (c is not None and backend is not None
                    and x.shape[1] == 1 and backend.pages_in_place(c))
                else None
                for c in (gcache or (None,) * len(g.kinds)))
            carried = any(pl is not None for pl in pools)
            rest = None if gcache is None else tuple(
                c if pl is None else
                {k: v for k, v in c.items() if k not in pl}
                for c, pl in zip(gcache, pools))

            moe = any(kind.moe for kind in g.kinds)

            def body(carry, xs, g=g, carried=carried, pools=pools, moe=moe):
                xc, pls = carry if carried else (carry, pools)
                lps, lcs, layer = xs if carried else xs + (None,)
                outs, new_pls, rs = [], [], 0
                for j, kind in enumerate(g.kinds):
                    lc = None if lcs is None else lcs[j]
                    if pls[j] is not None:
                        lc = {**lc, **pls[j], L.POOL_LAYER: layer}
                    xc, nc, r = make_lf(kind, g.mode, None)(xc, lps[j], lc)
                    rs = rs + r
                    if pls[j] is not None:
                        nc = {k: v for k, v in nc.items()
                              if k != L.POOL_LAYER}
                        new_pls.append({k: nc.pop(k) for k in pls[j]})
                    else:
                        new_pls.append(None)
                    outs.append(nc)
                ys = tuple(outs) if lcs is not None else None
                if moe:             # each layer's routed picks, as ys
                    ys = (ys, rs)
                return ((xc, tuple(new_pls)) if carried else xc), ys

            if carried:
                layer_ids = jnp.arange(g.steps, dtype=jnp.int32)
                (x, pools), nc_stack = jax.lax.scan(
                    body, (x, pools), (gp["layers"], rest, layer_ids))
            else:
                # per-layer caches (or none: an empty xs subtree) as xs/ys
                x, nc_stack = jax.lax.scan(body, x, (gp["layers"], rest))
            if moe:
                nc_stack, rs = nc_stack
                routed = routed + jnp.sum(rs)
            if gcache is not None:
                new_caches.append(tuple(
                    c if pl is None else {**c, **pl}
                    for c, pl in zip(nc_stack, pools)))
    return x, new_caches, routed


# ---------------------------------------------------------------------------
# full model forward
# ---------------------------------------------------------------------------


def embed_inputs(params, batch: dict, cfg: ArchConfig, *, positions,
                 compute_dtype, backend=None) -> jax.Array:
    """Map raw inputs to the first-layer activation per family."""
    emb = params["embed"]
    if cfg.frontend == "audio":
        x = L.dense(batch["frames"].astype(compute_dtype),
                    emb["frontend_proj"])
        return x
    x = L.embed(batch["tokens"], emb, cfg, positions=positions,
                segments=batch.get("segments"), compute_dtype=compute_dtype,
                backend=backend)
    if cfg.frontend == "vision" and "prefix_embeds" in batch:
        pfx = L.dense(batch["prefix_embeds"].astype(compute_dtype),
                      emb["frontend_proj"])
        if cfg.emb_scale_by_sqrt_dim:
            pfx = pfx * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
        x = jnp.concatenate([pfx, x], axis=1)
    return x


def unembed(x, params, cfg: ArchConfig) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"]["tok"].astype(x.dtype))
    else:
        logits = L.dense(x, params["lm_head"])
    return L.softcap(logits, cfg.final_softcap)


def forward(params, batch: dict, cfg: ArchConfig, plan: tuple[Group, ...],
            scheme: QuantScheme = QuantScheme(), *,
            obs: Optional[dict] = None, caches=None, pos=None, active=None,
            chunk: Optional[int] = DEFAULT_CHUNK,
            constrain: Constrain = _IDENTITY, remat: bool = False,
            compute_dtype=jnp.bfloat16, return_hidden: bool = False,
            return_routed: bool = False, pages=None, backend=None):
    """Full-sequence (train/prefill) or incremental (decode) forward.

    decode: pass ``caches`` + ``pos``: an int scalar (synchronized batch) or
    an (B,) int vector (continuous batching — per-row positions, with
    ``active`` (B,) bool gating cache/state writes of idle slots).
    ``backend``: a ComputeBackend (repro.kernels.backend) selecting the
    reference XLA or fused Pallas execution per quantized block.
    Returns (logits, new_caches), and the picks the held experts computed
    over all layers (an int32 scalar) third when ``return_routed``.
    """
    if cfg.frontend == "audio":
        S = batch["frames"].shape[1]
    else:
        S = batch["tokens"].shape[1]
        if cfg.frontend == "vision" and "prefix_embeds" in batch:
            S += batch["prefix_embeds"].shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    if pos is not None:
        pos = jnp.asarray(pos, jnp.int32)
        positions = (positions[None] + pos[:, None] if pos.ndim == 1
                     else positions + pos)
    x = embed_inputs(params, batch, cfg, positions=positions,
                     compute_dtype=compute_dtype,
                     backend=None if obs is not None else backend)
    x = constrain(x, "activation")
    x, new_caches, routed = run_groups(
        x, params, cfg, plan, scheme, positions=positions, obs=obs,
        caches=caches, chunk=chunk, constrain=constrain, remat=remat,
        active=active, pages=pages, backend=backend)
    x = L.norm(x, params["final_norm"], cfg.norm_kind)
    extra = (jnp.asarray(routed, jnp.int32),) if return_routed else ()
    if return_hidden or "head" in params:
        return (x, new_caches) + extra
    logits = constrain(unembed(x, params, cfg), "logits")
    return (logits, new_caches) + extra


# ---------------------------------------------------------------------------
# task heads + losses
# ---------------------------------------------------------------------------


def apply_head(hidden, params, kind: str):
    """Downstream-task module (paper §3.1): classification / matching pool
    the CLS position; NER tags every token."""
    if kind == "cls":
        pooled = jnp.tanh(L.dense(hidden[:, 0], params["head"]["pool"]))
        return L.dense(pooled, params["head"]["out"])
    if kind == "ner":
        return L.dense(hidden, params["head"]["out"])
    raise ValueError(f"unknown head kind {kind!r}")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None],
                             axis=-1)[..., 0] - lse
    nll = -ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


def lm_loss(params, batch: dict, cfg: ArchConfig, plan, scheme=QuantScheme(),
            *, constrain: Constrain = _IDENTITY, remat: bool = False,
            chunk: Optional[int] = DEFAULT_CHUNK,
            compute_dtype=jnp.bfloat16) -> jax.Array:
    """Next-token CE for decoder LMs; frame CE for audio; head CE for
    bert-family batches carrying a 'labels' of rank 1 (classification)."""
    if "head" in params:
        hidden, _ = forward(params, batch, cfg, plan, scheme,
                            constrain=constrain, remat=remat, chunk=chunk,
                            compute_dtype=compute_dtype)
        kind = "ner" if batch["labels"].ndim == 2 else "cls"
        logits = apply_head(hidden, params, kind)
        return cross_entropy(logits, batch["labels"])
    logits, _ = forward(params, batch, cfg, plan, scheme,
                        constrain=constrain, remat=remat, chunk=chunk,
                        compute_dtype=compute_dtype)
    if cfg.frontend == "audio":
        return cross_entropy(logits, batch["labels"])
    if cfg.frontend == "vision":
        # loss over the text region only
        P = batch["prefix_embeds"].shape[1]
        logits = logits[:, P:]
    tokens = batch["tokens"]
    return cross_entropy(logits[:, :-1], tokens[:, 1:])


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------


def _layer_cache(cfg: ArchConfig, kind: BlockKind, batch: int, max_len: int,
                 dtype, *, page_size: Optional[int] = None,
                 num_pages: int = 0, kv_scheme: str = "float",
                 lanes: int = 1):
    if kind.body == "attn":
        W = min(cfg.sliding_window, max_len) if kind.local else max_len
        paged = page_size is not None and not kind.local
        if paged:
            # pooled token pages + per-slot pos; the (B, pages_per_slot)
            # page table is a separate operand (PagePool), not a cache leaf.
            # Local layers keep the dense ring: it is already W-bounded.
            ps, NP = page_size, num_pages
            if cfg.mla is not None:
                if kv_scheme != "float":
                    # no kernel reads quantized latent pages yet: refuse,
                    # rather than serve float pages under an int8 name
                    raise ValueError(
                        f"{cfg.name}: MLA layers page their latent KV in "
                        f"float only; kv_cache={kv_scheme!r} is not "
                        f"supported (use kv_cache='float')")
                m = cfg.mla
                return {"pages_ckv": jnp.zeros((NP, ps, m.kv_lora_rank),
                                               dtype),
                        "pages_krope": jnp.zeros((NP, ps, m.qk_rope_dim),
                                                 dtype),
                        "pages_pos": jnp.full((NP, ps), -1, jnp.int32),
                        "pos": jnp.zeros((batch,), jnp.int32)}
            int8 = kv_scheme.startswith("int8")
            kv_dtype = jnp.int8 if int8 else dtype
            lanes = lanes if int8 else 1
            kv = L.page_leaf_shape(NP, ps, cfg.num_kv_heads, cfg.head_dim,
                                   lanes=lanes)
            d = {"pages_k": jnp.zeros(kv, kv_dtype),
                 "pages_v": jnp.zeros(kv, kv_dtype),
                 "pages_pos": jnp.full((NP, ps), -1, jnp.int32),
                 "pos": jnp.zeros((batch,), jnp.int32)}
            if kv_scheme == "int8_per_token":
                scales = L.page_leaf_shape(NP, ps, cfg.num_kv_heads,
                                           lanes=lanes)
                d["pages_ks"] = jnp.zeros(scales, jnp.float32)
                d["pages_vs"] = jnp.zeros(scales, jnp.float32)
            return d
        if cfg.mla is not None:
            m = cfg.mla
            return {"ckv": jnp.zeros((batch, W, m.kv_lora_rank), dtype),
                    "krope": jnp.zeros((batch, W, m.qk_rope_dim), dtype),
                    "k_pos": jnp.full((batch, W), -1, jnp.int32),
                    "pos": jnp.zeros((batch,), jnp.int32)}
        return {"k": jnp.zeros((batch, W, cfg.num_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((batch, W, cfg.num_kv_heads, cfg.head_dim),
                               dtype),
                "k_pos": jnp.full((batch, W), -1, jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32)}
    if kind.body == "rglru":
        return R.init_state(cfg, batch, dtype)
    if kind.body == "mlstm":
        return X.mlstm_state(cfg, batch, dtype)
    return X.slstm_state(cfg, batch, dtype)


def pages_per_slot(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def init_caches(cfg: ArchConfig, plan: tuple[Group, ...],
                batch: int, max_len: int, dtype=jnp.bfloat16, *,
                page_size: Optional[int] = None,
                num_pages: Optional[int] = None,
                kv_schemes: Optional[Sequence[str]] = None,
                lanes: int = 1):
    """Decode-cache pytree mirroring the plan's group structure. Cache
    geometry is fully determined by (cfg, plan, batch, max_len) plus the
    paged-KV knobs — no parameters needed.

    ``page_size`` switches full-attention layers to the paged layout (see
    repro.models.layers, paged-KV section); ``num_pages`` sizes the shared
    page pool (default ``batch * pages_per_slot`` — no oversubscription);
    ``kv_schemes`` gives each layer's KV-cache scheme from the
    PrecisionPlan (``plan_obj.kv_schemes``), default all-float. Scan groups
    are homogeneous by construction (group_boundaries splits on full
    LayerPlan equality, which includes ``kv_cache``). ``lanes`` pads the
    minor dim of int8 pages to the lane width of the kernels that read
    them (``ComputeBackend.page_lanes``)."""
    if page_size is not None and num_pages is None:
        num_pages = batch * pages_per_slot(max_len, page_size)
    caches = []
    for g in plan:
        for li in range(g.start, g.stop):
            if kv_schemes is not None and \
                    kv_schemes[li] != kv_schemes[g.start]:
                raise ValueError(
                    f"kv_cache scheme changes inside scan group "
                    f"[{g.start}, {g.stop}) at layer {li}; rebuild the "
                    f"execution plan from the PrecisionPlan")
        scheme = kv_schemes[g.start] if kv_schemes is not None else "float"
        period = []
        for kind in g.kinds:
            one = _layer_cache(cfg, kind, batch, max_len, dtype,
                               page_size=page_size, num_pages=num_pages or 0,
                               kv_scheme=scheme, lanes=lanes)
            period.append(jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (g.steps,) + a.shape), one))
        caches.append(tuple(period))
    return caches


def cache_bytes(caches) -> int:
    """Total KV/state cache footprint in bytes (the serving-side
    ``samp_kv_cache_bytes`` gauge)."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(caches)))


def kv_geometry(caches) -> tuple:
    """Structural (scheme, page_size, num_pages) summary of a cache tree —
    part of the runtime's executable-cache key, so float/int8 and different
    page geometries never share a compiled decode step."""
    ps, np_ = None, None
    has_scales, has_int8 = False, False
    for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
        name = str(path[-1])
        if "pages_pos" in name:
            np_, ps = (int(s) for s in leaf.shape[-2:])
        elif ("pages_ks" in name) or ("pages_vs" in name):
            has_scales = True
        elif ("pages_k" in name or "pages_v" in name) \
                and leaf.dtype == jnp.int8:
            has_int8 = True
    scheme = ("int8_per_token" if has_scales
              else "int8_per_head" if has_int8 else "float")
    return (scheme, ps, np_)


def decode_step(params, tokens, caches, pos, cfg: ArchConfig, plan,
                scheme: QuantScheme = QuantScheme(), *, active=None,
                constrain: Constrain = _IDENTITY,
                compute_dtype=jnp.bfloat16, pages=None, backend=None,
                return_routed: bool = False):
    """One serving step: tokens (B, 1) at absolute position(s) ``pos``
    (scalar = synchronized batch; (B,) vector = continuous batching, with
    ``active`` gating idle slots: their tokens route to no expert).
    ``pages`` is the scheduler's (B, pages_per_slot) page table when the
    caches are paged. Returns (logits (B, 1, V), new_caches), and the
    active tokens' picks the held experts computed third when
    ``return_routed``."""
    return forward(params, {"tokens": tokens}, cfg, plan, scheme,
                   caches=caches, pos=pos, active=active, chunk=None,
                   constrain=constrain, compute_dtype=compute_dtype,
                   pages=pages, backend=backend,
                   return_routed=return_routed)
