"""Post-training quantization: apply a SAMP precision plan to float params.

The flow (paper §3.2 / Appendix A):

    float params --capture_stats(calibration batches)--> amax per (layer, site)
                 --apply_plan(PrecisionPlan)--> mixed-precision params + plan

Precision is described by a :class:`~repro.core.plan.PrecisionPlan`: per
layer, per GEMM *block* (qkv / attn_out / ffn_in / ffn_out), a
:class:`~repro.core.plan.QuantSpec` names the weight scheme
(int8-per-channel — pytorch-quantization's weight default — or
int8-per-tensor), the activation scheme (static per-tensor scales from the
calibrator, the paper's scheme, or per-token dynamic — then no ``xs`` is
stored and :func:`repro.models.layers.dense` quantizes at runtime), and the
calibrator that turns observed ranges into amax values
(:func:`repro.core.calibration.make_calibrator`).

Which weights belong to which block per layer kind — and which activation
sites feed them — is the :data:`SITE_MAP` below; attention's batched
matmuls (q·k^T, p·v) additionally get ``{q,k,p,v}_scale`` scalars when the
layer's qkv block is statically quantized (the paper's Figure-2(a) path,
including the softmax quantization that Appendix B shows is the accuracy
killer).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import inspect

from repro.configs.base import ArchConfig, BlockKind
from repro.core.calibration import CALIBRATORS, Calibrator, make_calibrator
from repro.core.plan import LayerPlan, PrecisionPlan
from repro.core.quantize import (QuantizedTensor, compute_scale_symmetric,
                                 quantize, UINT8_MAX)
from repro.models import transformer as T

# (group, param_path, site, block): group 'mha'/'ffn' names the paper's GEMM
# group, ``block`` the PrecisionPlan block whose QuantSpec governs the
# weight. Paths are within the layer dict; ``site`` is the activation
# observation feeding the GEMM.
SITE_MAP: dict[str, list[tuple[str, tuple[str, ...], str, str]]] = {
    "attn": [
        ("mha", ("attn", "wq"), "attn_in", "qkv"),
        ("mha", ("attn", "wk"), "attn_in", "qkv"),
        ("mha", ("attn", "wv"), "attn_in", "qkv"),
        ("mha", ("attn", "wo"), "attn_out", "attn_out"),
    ],
    "attn_mla": [
        ("mha", ("attn", "wq_a"), "attn_in", "qkv"),
        ("mha", ("attn", "wq_b"), "q_lat", "qkv"),
        ("mha", ("attn", "wq"), "attn_in", "qkv"),   # q_lora_rank == 0
        ("mha", ("attn", "wkv_a"), "attn_in", "qkv"),
        ("mha", ("attn", "wkv_b"), "c_kv", "qkv"),
        ("mha", ("attn", "wo"), "attn_out", "attn_out"),
    ],
    "ffn_glu": [
        ("ffn", ("ffn", "wg"), "ffn_in", "ffn_in"),
        ("ffn", ("ffn", "wu"), "ffn_in", "ffn_in"),
        ("ffn", ("ffn", "wd"), "ffn_hidden", "ffn_out"),
    ],
    "ffn_gelu": [
        ("ffn", ("ffn", "wi"), "ffn_in", "ffn_in"),
        ("ffn", ("ffn", "wo"), "ffn_hidden", "ffn_out"),
    ],
    "moe": [
        ("ffn", ("ffn", "wg"), "ffn_in_e", "ffn_in"),
        ("ffn", ("ffn", "wu"), "ffn_in_e", "ffn_in"),
        ("ffn", ("ffn", "wd"), "ffn_hidden", "ffn_out"),
        ("ffn", ("ffn", "shared", "wg"), "shared_ffn_in", "ffn_in"),
        ("ffn", ("ffn", "shared", "wu"), "shared_ffn_in", "ffn_in"),
        ("ffn", ("ffn", "shared", "wd"), "shared_ffn_hidden", "ffn_out"),
    ],
    "rglru": [
        ("ffn", ("rec", "wx"), "rec_in", "ffn_in"),
        ("ffn", ("rec", "wg"), "rec_in", "ffn_in"),
        ("ffn", ("rec", "wa"), "rec_gate_in", "ffn_in"),
        ("ffn", ("rec", "wi"), "rec_gate_in", "ffn_in"),
        ("ffn", ("rec", "wo"), "rec_out", "ffn_out"),
    ],
    "mlstm": [
        ("ffn", ("blk", "up"), "blk_in", "ffn_in"),
        ("ffn", ("blk", "wq"), "qkv_in", "ffn_in"),
        ("ffn", ("blk", "wk"), "qkv_in", "ffn_in"),
        ("ffn", ("blk", "wif"), "qkv_in", "ffn_in"),
        ("ffn", ("blk", "wv"), "xm", "ffn_in"),
        ("ffn", ("blk", "down"), "blk_hidden", "ffn_out"),
    ],
    "slstm": [
        ("ffn", ("blk", "wz"), "blk_in", "ffn_in"),
        ("ffn", ("blk", "wo"), "blk_in", "ffn_in"),
        ("ffn", ("blk", "wi"), "blk_conv_in", "ffn_in"),
        ("ffn", ("blk", "wf"), "blk_conv_in", "ffn_in"),
        ("ffn", ("blk", "proj"), "blk_hidden", "ffn_out"),
    ],
}

BMM_SITES = ("q", "k", "p", "v")    # attention batched-matmul operands

# site name -> plan block, derived from the map above; the attention bmm
# operands ride the qkv block's spec (they are inside the MHA group).
SITE_BLOCK: dict[str, str] = {
    site: block
    for entries in SITE_MAP.values()
    for (_g, _p, site, block) in entries
}
SITE_BLOCK.update({s: "qkv" for s in BMM_SITES})
# pre-norm residual delta (the attn_out GEMM's *output*): calibrates the
# requant scale that lets the whole-layer int8 span (LayerPlan.norm='int8')
# hand the fused add+norm an int8 delta. Rides the attn_out block's spec.
SITE_BLOCK["attn_delta"] = "attn_out"
# schema-v4 block families: the per-expert vector sites recorded inside the
# routed _expert_gemm (amax over each expert's capacity buffer, shape (E,))
# and the shared-expert scalar sites ride their family's spec —
# LayerPlan.spec resolves the family with its documented fallback when the
# plan predates v4.
SITE_BLOCK["expert_in"] = "experts"
SITE_BLOCK["expert_hidden"] = "experts"


def _entry_spec(layer: LayerPlan, kind: BlockKind, path: tuple[str, ...],
                block: str):
    """Resolve the QuantSpec governing one SITE_MAP entry, honoring the
    schema-v4 block families on MoE layers.

    Returns ``(spec, expert_site)``: ``expert_site`` names the per-expert
    vector amax site ('expert_in' / 'expert_hidden') when the entry is a
    routed expert GEMM under the ``experts`` family (static activation
    scales then become per-expert, shape (E, 1, 1)), else ``None``.
    """
    if kind.moe and path and path[0] == "ffn" and len(path) >= 2:
        if path[1] == "shared":
            if layer.shared_ffn is not None:
                return layer.shared_ffn, None
        elif path[1] in ("wg", "wu", "wd") and layer.experts is not None:
            site = "expert_hidden" if path[1] == "wd" else "expert_in"
            return layer.experts, site
    return layer.spec(block), None


def _kind_entries(cfg: ArchConfig, kind: BlockKind):
    entries = []
    if kind.body == "attn":
        entries += SITE_MAP["attn_mla" if cfg.mla is not None else "attn"]
        entries += SITE_MAP["moe" if kind.moe else
                            ("ffn_glu" if cfg.ffn_kind == "glu" else "ffn_gelu")]
    elif kind.body == "rglru":
        entries += SITE_MAP["rglru"]
        entries += SITE_MAP["ffn_glu" if cfg.ffn_kind == "glu" else "ffn_gelu"]
    else:
        entries += SITE_MAP[kind.body]
    return entries


def quantize_weight(w: jax.Array,
                    scheme: str = "int8_per_channel") -> QuantizedTensor:
    """Symmetric int8 weight quantization under a named scheme.

    * ``int8_per_channel`` — per-output-channel (pytorch-quantization's
      weight default). 2-D (K, N): scale (1, N); 3-D expert stacks
      (E, K, N): per-expert-per-channel scale (E, 1, N).
    * ``int8_per_tensor`` — one scale for the whole tensor (shape
      (1,) * ndim so dequant broadcasting stays uniform).
    """
    if scheme == "int8_per_tensor":
        amax = jnp.max(jnp.abs(w)).reshape((1,) * w.ndim)
    elif scheme == "int8_per_channel":
        reduce_axes = (w.ndim - 2,) if w.ndim == 3 else tuple(range(w.ndim - 1))
        amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    else:
        raise ValueError(f"unknown weight scheme {scheme!r}")
    scale = compute_scale_symmetric(amax)
    return QuantizedTensor(quantize(w, scale), scale, None)


def _get_path(d: dict, path: tuple[str, ...]):
    for k in path:
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


def _set_path(d: dict, path: tuple[str, ...], value) -> None:
    for k in path[:-1]:
        d = d[k]
    d[path[-1]] = value


def quantize_layer(lp: dict, cfg: ArchConfig, kind: BlockKind,
                   layer: LayerPlan, amax: dict[str, float],
                   scheme: T.QuantScheme) -> dict:
    """Return a quantized copy of one layer's params under its per-block
    :class:`LayerPlan`. ``amax`` maps site name -> calibrated amax for THIS
    layer."""
    if not (layer.quant_mha or layer.quant_ffn
            or layer.kv_cache != "float"):
        return lp
    lp = _copy_dicts(lp)                     # containers copied, leaves shared
    for group, path, site, block in _kind_entries(cfg, kind):
        spec, expert_site = _entry_spec(layer, kind, path, block)
        if not spec.quantized:
            continue
        sub = _get_path(lp, path)
        if sub is None:
            continue
        new = dict(sub)
        new["w"] = quantize_weight(sub["w"], spec.weight)
        if spec.static_acts:
            if expert_site is not None:
                # experts family: per-expert static scales from the (E,)
                # vector amax recorded inside the routed _expert_gemm;
                # shaped (E, 1, 1) to broadcast against (..., E, C, D)
                if expert_site not in amax:
                    raise ValueError(
                        f"experts family with act='int8_per_tensor' needs "
                        f"calibrated {expert_site!r} stats for this layer; "
                        f"re-run capture_stats (or use act="
                        f"'int8_per_token')")
                vec = jnp.asarray(amax[expert_site], jnp.float32)
                new["xs"] = compute_scale_symmetric(vec).reshape(-1, 1, 1)
            elif site in amax:
                new["xs"] = jnp.asarray(
                    compute_scale_symmetric(jnp.float32(amax[site])))
        _set_path(lp, path, new)
    if kind.body == "attn" and layer.qkv.quantized and layer.qkv.static_acts:
        attn = lp["attn"]
        for s in BMM_SITES:
            if s not in amax:
                continue
            if s == "p" and (scheme.softmax_mode == "unsigned"
                             or layer.softmax == "uint8"):
                # softmax outputs live in [0, 1]: asymmetric unsigned scale
                # (amax/255, zero point -128) uses the full code space —
                # LayerPlan.softmax='uint8' forces it per layer even when
                # the global scheme knob stays symmetric
                sc = jnp.float32(max(amax[s], 1e-8)) / UINT8_MAX
            else:
                sc = compute_scale_symmetric(jnp.float32(amax[s]))
            attn[f"{s}_scale"] = jnp.asarray(sc)
    elif (kind.body == "attn" and layer.softmax == "uint8"
          and "p" in amax):
        # decode-side softmax quantization (int8 KV, float qkv block): the
        # fused decode kernel re-quantizes the probabilities with p_scale
        lp["attn"]["p_scale"] = jnp.asarray(
            jnp.float32(max(amax["p"], 1e-8)) / UINT8_MAX)
    if kind.body == "attn" and layer.norm == "int8":
        # whole-layer int8 span: the attn_out GEMM re-quantizes its output
        # (the pre-norm residual delta) so the fused add+norm consumes int8
        if "attn_delta" not in amax:
            raise ValueError(
                "norm='int8' needs calibrated attn_delta stats for this "
                "layer; re-run capture_stats on this plan")
        wo = dict(lp["attn"]["wo"])
        wo["out_xs"] = jnp.asarray(
            compute_scale_symmetric(jnp.float32(amax["attn_delta"])))
        lp["attn"]["wo"] = wo
        if (cfg.ffn_kind != "glu" and not kind.moe
                and layer.ffn_out.quantized and layer.ffn_out.static_acts
                and "ffn_hidden" in amax):
            # extend the span through the FFN: wi re-quantizes its GELU'd
            # hidden at the scale wo already consumes it at (its own xs) —
            # the boundary is numerics-neutral through wo. GLU hiddens are
            # the product of two GEMMs and keep the float boundary.
            wi = dict(lp["ffn"]["wi"])
            wi["out_xs"] = jnp.asarray(
                compute_scale_symmetric(jnp.float32(amax["ffn_hidden"])))
            lp["ffn"]["wi"] = wi
    if kind.body == "attn" and layer.kv_cache == "int8_per_head":
        # static KV-cache scales: the per-head amax vectors recorded by
        # observe_per_head at the k_cache/v_cache sites (post-rope)
        attn = lp["attn"]
        for key, site in (("k", "k_cache"), ("v", "v_cache")):
            if site not in amax:
                raise ValueError(
                    f"kv_cache='int8_per_head' needs calibrated {site} "
                    f"stats for this layer; re-run capture_stats on this "
                    f"plan (or use kv_cache='int8_per_token')")
            attn[f"{key}c_scale"] = jnp.asarray(compute_scale_symmetric(
                jnp.asarray(amax[site], jnp.float32)))
    return lp


def _copy_dicts(tree):
    if isinstance(tree, dict):
        return {k: _copy_dicts(v) for k, v in tree.items()}
    if isinstance(tree, tuple):
        return tuple(_copy_dicts(v) for v in tree)
    if isinstance(tree, list):
        return [_copy_dicts(v) for v in tree]
    return tree


# ---------------------------------------------------------------------------
# calibration capture
# ---------------------------------------------------------------------------

HIST_SITES = ("attn_in", "attn_out", "attn_delta", "ffn_in", "ffn_hidden",
              "p")


def capture_stats(params: dict, batches: Sequence[dict], cfg: ArchConfig,
                  plan, scheme: T.QuantScheme = T.QuantScheme(), *,
                  calibrator: Optional[str] = None,
                  precision: Optional[PrecisionPlan] = None,
                  hist_sites: tuple[str, ...] = HIST_SITES,
                  compute_dtype=jnp.float32,
                  clusters: Optional[Sequence] = None,
                  **calib_kw) -> dict[str, dict[str, float]]:
    """Run calibration batches through the float model with observers on and
    reduce per-(layer, site) statistics to amax values.

    Calibrator selection, in precedence order:

    * ``calibrator=`` — one calibrator name for every site (the paper's
      workflow; ``"minmax"`` consumes the cheap per-batch scalar amax
      observations and works at any model size);
    * ``precision=`` — a :class:`PrecisionPlan` whose per-block
      ``QuantSpec.calibrator`` choices are honored per (layer, site) via
      :data:`SITE_BLOCK`;
    * neither — min-max everywhere.

    Histogram calibrators (percentile/mse/entropy) additionally consume raw
    values on ``hist_sites`` — that path materializes activations and is
    intended for calibration-size models only; sites without raw captures
    fall back to the scalar minmax amax.

    Sharded calibration (params/batches placed over a mesh, e.g. batches
    data-parallel over the ``data`` axis) needs no special handling: the
    observers record ``jnp.max(|x|)`` — a *global* reduction, so a batch
    sharded over the data axis yields exactly the amax of the whole batch,
    and replicated params observe identical values on every shard.
    ``tests/test_mesh_serving.py`` pins sharded == unsharded stats.

    Returns {"layer{i}": {site: amax}}.

    Cluster-conditional capture (the input-adaptive path, see
    :mod:`repro.adaptive`): ``clusters=`` is a sequence aligned with
    ``batches`` of per-row cluster-id vectors (shape (B,), ints). Rows are
    partitioned into cluster-pure sub-batches and the observers aggregate
    per (cluster, layer, site) — including the per-head ``k_cache`` /
    ``v_cache`` vector sites. Because every observation is a max-reduction,
    partitioning rows is *exact*: each cluster's amax is the amax over
    precisely its own rows. The return shape becomes
    ``{cluster_id: {"layer{i}": {site: amax}}}``. When ``precision`` is a
    :class:`~repro.core.plan.PlanSet`, each cluster's member plan governs
    its calibrator selection.
    """
    if clusters is not None:
        return _capture_stats_clustered(
            params, batches, cfg, plan, scheme, clusters,
            calibrator=calibrator, precision=precision,
            hist_sites=hist_sites, compute_dtype=compute_dtype, **calib_kw)

    def site_calibrator(layer_idx: int, site: str) -> str:
        if calibrator is not None:
            return calibrator
        if precision is not None:
            block = SITE_BLOCK.get(site)
            if block is not None and layer_idx < precision.num_layers:
                spec = precision.layers[layer_idx].spec(block)
                if spec.quantized:
                    return spec.calibrator
        return "minmax"

    if calibrator is not None:
        use_hist = calibrator != "minmax"
    else:
        use_hist = precision is not None and any(
            s is not None and s.quantized and s.calibrator != "minmax"
            for lp in precision.layers for s in
            (lp.qkv, lp.attn_out, lp.ffn_in, lp.ffn_out,
             lp.experts, lp.shared_ffn))

    def calibrator_kw(name: str) -> dict:
        # a plan may mix calibrator families in one capture run; hand each
        # constructor only the kwargs it accepts (percentile= must not
        # crash the MSE calibrator on another block)
        accepted = inspect.signature(CALIBRATORS[name].__init__).parameters
        return {k: v for k, v in calib_kw.items() if k in accepted}

    cals: dict[str, Calibrator] = {}
    scalar_amax: dict = {}          # float per scalar site, (H,) per-head

    for batch in batches:
        obs: dict = {}
        if use_hist:
            obs["__values__"] = True
        # capture mode forces unrolled execution (see transformer.run_groups)
        quant_probe = dataclasses.replace(scheme)
        T.forward(params, batch, cfg, plan, quant_probe, obs=obs,
                  compute_dtype=compute_dtype)
        raw = obs.pop("__raw__", {}) if use_hist else {}
        obs.pop("__values__", None)
        for key, v in obs.items():
            if key.startswith("layer"):
                v = np.asarray(v, np.float32)
                if v.ndim:          # per-head sites (k_cache/v_cache): (H,)
                    prev = scalar_amax.get(key)
                    scalar_amax[key] = (v if prev is None
                                        else np.maximum(np.asarray(prev), v))
                else:
                    scalar_amax[key] = max(scalar_amax.get(key, 0.0),
                                           float(v))
        for key, v in raw.items():
            layer, site = key.split("/", 1)
            if site not in hist_sites:
                continue
            name = site_calibrator(int(layer[len("layer"):]), site)
            if name == "minmax":
                continue            # scalar running max already covers it
            cals.setdefault(key, make_calibrator(name, **calibrator_kw(name))
                            ).observe(np.asarray(v))

    out: dict[str, dict[str, float]] = {}
    for key, amax in scalar_amax.items():
        layer, site = key.split("/", 1)
        # vector (per-head) stats are emitted as plain lists so the stats
        # dict stays JSON-round-trippable through toolkit.artifact
        out.setdefault(layer, {})[site] = (
            [float(x) for x in amax] if isinstance(amax, np.ndarray)
            else amax)
    for key, cal in cals.items():
        layer, site = key.split("/", 1)
        out.setdefault(layer, {})[site] = float(cal.compute_amax())
    return out


def _capture_stats_clustered(params, batches, cfg, plan, scheme, clusters,
                             *, precision=None, **kw):
    """Partition calibration rows by cluster id and capture per-cluster
    stats (see :func:`capture_stats`). ``precision`` may be a PlanSet —
    each cluster then calibrates under its own member plan."""
    from repro.core.plan import PlanSet
    ids = [np.asarray(c).reshape(-1).astype(np.int64) for c in clusters]
    if len(ids) != len(batches):
        raise ValueError(f"clusters has {len(ids)} entries for "
                         f"{len(batches)} batches")
    groups: dict[int, list] = {}
    for batch, cid in zip(batches, ids):
        sizes = {np.asarray(v).shape[0] for v in jax.tree_util.tree_leaves(
            batch)}
        if sizes != {len(cid)}:
            raise ValueError(f"cluster-id vector of length {len(cid)} does "
                             f"not match batch row counts {sorted(sizes)}")
        for c in sorted({int(x) for x in cid}):
            rows = np.nonzero(cid == c)[0]
            sub = jax.tree_util.tree_map(lambda a: np.asarray(a)[rows],
                                         batch)
            groups.setdefault(c, []).append(sub)
    out = {}
    for c, bs in sorted(groups.items()):
        member = (precision.plan_for(c)
                  if isinstance(precision, PlanSet) else precision)
        out[c] = capture_stats(params, bs, cfg, plan, scheme,
                               precision=member, **kw)
    return out


def apply_plan(params: dict, cfg: ArchConfig,
               precision: PrecisionPlan,
               stats: dict[str, dict[str, float]], *,
               scheme: T.QuantScheme = T.QuantScheme(),
               float_plan=None, backend=None):
    """float params (packed under ``float_plan``) + calibration stats
    -> (quantized params packed under the plan's execution plan, that
    execution plan). The PrecisionPlan entry point every consumer uses.

    ``backend`` (a name or ComputeBackend from
    :mod:`repro.kernels.backend`) validates up front that every spec the
    plan names passes the deployment backend's ``supports()`` check — the
    built-in backends execute everything (reference ops are the universal
    fallback), so this is the fail-fast hook for custom registered
    backends with a narrower op set."""
    if backend is not None:
        from repro.kernels.backend import get_backend
        get_backend(backend).validate_plan(precision)
    if precision.num_layers != cfg.num_layers:
        raise ValueError(f"plan has {precision.num_layers} layers, arch "
                         f"{cfg.num_layers}")
    float_plan = float_plan or T.build_plan(
        cfg, PrecisionPlan.full_float(cfg.num_layers, precision.float_dtype))
    new_plan = T.build_plan(cfg, precision)
    kinds = cfg.layer_kinds()

    def transform(i: int, lp: dict) -> dict:
        return quantize_layer(lp, cfg, kinds[i], precision.layers[i],
                              stats.get(f"layer{i}", {}), scheme)

    qparams = T.repack(params, float_plan, new_plan, transform)
    return qparams, new_plan

