"""Quantized artifact bundles: deploy a tuned model without re-calibration.

An artifact is everything SAMP chose plus everything PTQ produced, saved as
one directory:

* ``artifact.json``  — the architecture config, the chosen
  :class:`~repro.core.plan.PrecisionPlan` (with its ``fingerprint`` recorded
  for integrity checks), the quantization scheme, the calibration stats
  (per-layer/site amax values), the task + target head identity, and the
  parameter dtype;
* ``step_00000000/`` — every parameter leaf (int8 weights, scales, float
  residue) written through :mod:`repro.checkpoint.store` (atomic rename,
  template-addressed leaves).

Loading reconstructs the exact parameter *structure* from the metadata —
float init -> ``ptq.apply_plan`` with the saved stats/plan gives a
template with the same QuantizedTensor layout — then restores the saved
leaves into it. Outputs are bit-identical to the pipeline that was saved,
the reloaded plan's ``fingerprint()`` is byte-identical to the recorded
one, and no calibration batches are needed at deployment time.

Version history: v1 bundles stored the paper's per-layer modes
(``policy`` key); they still load, each mode expanded through
:meth:`~repro.core.plan.LayerPlan.for_mode`. v3 bundles are
*adaptive*: they persist the FLOAT parameters plus a
:class:`~repro.core.plan.PlanSet`, a serialized cluster model, and
per-cluster calibration stats — loading rebuilds the K quantized trees
deterministically via ``ptq.apply_plan`` (bit-identical to what was
served, still no calibration batches) and can hand back a
:class:`~repro.adaptive.PlanRouter`. Single-plan bundles keep writing v2,
so existing deployments and fingerprints are untouched.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import store
from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                RopeScaling)
from repro.core.plan import LayerMode, LayerPlan, PrecisionPlan
from repro.data.pipeline import TaskSpec
from repro.models import transformer as T
from repro.quant import ptq
from repro.toolkit.registry import get_target

METADATA = "artifact.json"
VERSION = 3                 # current max readable version
SINGLE_PLAN_VERSION = 2     # what save_artifact writes (unchanged by v3)


@dataclasses.dataclass
class Artifact:
    """A loaded bundle, ready to serve."""
    cfg: ArchConfig
    precision: PrecisionPlan
    scheme: T.QuantScheme
    stats: dict
    params: dict
    plan: tuple
    task: Optional[TaskSpec]
    target_name: str
    n_out: int
    path: str
    compute_dtype: str = "float32"
    tokenizer: Optional[object] = None       # WordPieceTokenizer
    # v3 adaptive bundles only:
    planset: Optional[object] = None         # PlanSet
    cluster_model: Optional[object] = None   # repro.adaptive ClusterModel
    cluster_stats: Optional[dict] = None     # {cluster: {layer: {site: v}}}
    float_params: Optional[dict] = None      # the shared float weight tree

    @property
    def adaptive(self) -> bool:
        return self.planset is not None

    def router(self, backend=None):
        """Rebuild the :class:`~repro.adaptive.PlanRouter` a v3 bundle was
        deployed with: each member plan re-quantizes the shared float tree
        under its own cluster's stats (deterministic — bit-identical to the
        trees that were served)."""
        if not self.adaptive:
            raise ValueError(f"{self.path}: not an adaptive (v3) bundle — "
                             f"no PlanSet to route over")
        from repro.adaptive import build_router
        return build_router(self.cfg, self.float_params, self.planset,
                            self.cluster_stats,
                            cluster_model=self.cluster_model,
                            scheme=self.scheme, backend=backend)

    @property
    def policy(self) -> PrecisionPlan:
        """The precision description (kept under the pre-plan name)."""
        return self.precision

    def pipeline(self, backend: str = "reference", mesh=None):
        """Rebuild the (quantized) Pipeline this artifact was saved from.
        ``backend`` picks the compute backend and ``mesh`` the serving
        topology (both deployment-time choices — the bundle persists the
        plan, not how or where it executes)."""
        from repro.toolkit.pipeline import Pipeline
        task = self.task or TaskSpec(name="lm", kind="lm", n_classes=0,
                                     vocab_size=self.cfg.vocab_size,
                                     seq_len=64)
        float_pipe = Pipeline(self.cfg, task, get_target(self.target_name),
                              n_out=self.n_out, scheme=self.scheme,
                              tokenizer=self.tokenizer,
                              compute_dtype=jnp.dtype(self.compute_dtype),
                              backend=backend, mesh=mesh)
        return float_pipe.with_policy(self.params, self.plan, self.precision)


def _cfg_to_dict(cfg: ArchConfig) -> dict:
    return dataclasses.asdict(cfg)


def _cfg_from_dict(d: dict) -> ArchConfig:
    d = dict(d)
    if d.get("moe"):
        d["moe"] = MoEConfig(**d["moe"])
    if d.get("mla"):
        d["mla"] = MLAConfig(**d["mla"])
    if d.get("rope_scaling"):
        d["rope_scaling"] = RopeScaling(**d["rope_scaling"])
    d["pattern"] = tuple(d["pattern"])
    return ArchConfig(**d)


def _param_dtype(params: dict) -> str:
    for leaf in jax.tree_util.tree_leaves(params):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return str(jnp.asarray(leaf).dtype)
    return "float32"


def save_artifact(directory: str, *, cfg: ArchConfig,
                  policy: PrecisionPlan, stats: dict, params: dict,
                  scheme: T.QuantScheme = T.QuantScheme(),
                  task: Optional[TaskSpec] = None,
                  target: str = "lm", n_out: int = 0,
                  compute_dtype: str = "float32",
                  tokenizer=None) -> str:
    """Write a deployable bundle. ``params`` must be the PTQ output for
    the plan ``policy`` packed under its execution plan; ``stats`` the
    calibration stats the plan was applied with."""
    os.makedirs(directory, exist_ok=True)
    meta = {
        "version": SINGLE_PLAN_VERSION,
        "arch": _cfg_to_dict(cfg),
        "plan": policy.to_dict(),
        "plan_fingerprint": policy.fingerprint(),
        "scheme": dataclasses.asdict(scheme),
        "stats": stats,
        "task": dataclasses.asdict(task) if task is not None else None,
        "target": {"name": target, "n_out": n_out},
        "param_dtype": _param_dtype(params),
        "compute_dtype": str(jnp.dtype(compute_dtype)),
        "tokenizer": ({"vocab": tokenizer.vocab,
                       "granularity": tokenizer.granularity}
                      if tokenizer is not None else None),
    }
    tmp = os.path.join(directory, METADATA + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=1)
    os.rename(tmp, os.path.join(directory, METADATA))
    store.save(directory, 0, params, keep_last=1)
    return directory


def save_adaptive_artifact(directory: str, *, cfg: ArchConfig, planset,
                           cluster_model, cluster_stats: dict,
                           float_params: dict,
                           scheme: T.QuantScheme = T.QuantScheme(),
                           task: Optional[TaskSpec] = None,
                           target: str = "lm", n_out: int = 0,
                           compute_dtype: str = "float32",
                           tokenizer=None) -> str:
    """Write an adaptive (v3) bundle: the FLOAT parameter tree plus the
    PlanSet, the cluster model, and the per-cluster calibration stats.
    The K quantized trees are NOT stored — ``load_artifact`` rebuilds them
    deterministically with ``ptq.apply_plan`` (bit-identical, since the
    inputs are identical)."""
    if set(cluster_stats) - set(planset.cluster_ids):
        raise ValueError(f"cluster_stats covers {sorted(cluster_stats)} but "
                         f"the planset only {list(planset.cluster_ids)}")
    os.makedirs(directory, exist_ok=True)
    meta = {
        "version": 3,
        "arch": _cfg_to_dict(cfg),
        "planset": planset.to_dict(),
        "planset_fingerprint": planset.fingerprint(),
        "cluster_model": cluster_model.to_dict(),
        "cluster_model_fingerprint": cluster_model.fingerprint(),
        "scheme": dataclasses.asdict(scheme),
        # JSON objects key on strings; load restores the int cluster ids
        "cluster_stats": {str(c): s for c, s in cluster_stats.items()},
        "task": dataclasses.asdict(task) if task is not None else None,
        "target": {"name": target, "n_out": n_out},
        "param_dtype": _param_dtype(float_params),
        "compute_dtype": str(jnp.dtype(compute_dtype)),
        "tokenizer": ({"vocab": tokenizer.vocab,
                       "granularity": tokenizer.granularity}
                      if tokenizer is not None else None),
    }
    tmp = os.path.join(directory, METADATA + ".tmp")
    with open(tmp, "w") as f:
        json.dump(meta, f, indent=1)
    os.rename(tmp, os.path.join(directory, METADATA))
    store.save(directory, 0, float_params, keep_last=1)
    return directory


def _coerce_stats(sites_by_layer: dict) -> dict:
    # per-head KV-cache stats round-trip as lists; everything else is scalar
    return {layer: {site: (v if isinstance(v, list) else float(v))
                    for site, v in sites.items()}
            for layer, sites in sites_by_layer.items()}


def _precision_from_meta(meta: dict) -> PrecisionPlan:
    if meta["version"] >= 2:
        precision = PrecisionPlan.from_dict(meta["plan"])
        want = meta.get("plan_fingerprint")
        if want is not None and precision.fingerprint() != want:
            raise ValueError(
                f"plan fingerprint mismatch: metadata says {want}, "
                f"reloaded plan hashes to {precision.fingerprint()} — "
                f"the bundle's artifact.json was edited or corrupted")
        return precision
    # v1: the paper's per-layer modes + float_dtype
    dynamic_acts = T.QuantScheme(**meta["scheme"]).dynamic_acts
    return PrecisionPlan(
        tuple(LayerPlan.for_mode(LayerMode(m), dynamic_acts=dynamic_acts)
              for m in meta["policy"]["modes"]),
        meta["policy"]["float_dtype"])


def load_artifact(directory: str) -> Artifact:
    """Reload a bundle: rebuild the quantized parameter structure from the
    saved plan + stats, then restore the leaves. No re-calibration."""
    with open(os.path.join(directory, METADATA)) as f:
        meta = json.load(f)
    if not 1 <= meta["version"] <= VERSION:
        raise ValueError(f"artifact version {meta['version']} not in "
                         f"[1, {VERSION}]")
    cfg = _cfg_from_dict(meta["arch"])
    adaptive = meta["version"] >= 3
    planset = cluster_model = cluster_stats = None
    if adaptive:
        from repro.adaptive import PlanSet, cluster_model_from_dict
        planset = PlanSet.from_dict(meta["planset"])
        want = meta.get("planset_fingerprint")
        if want is not None and planset.fingerprint() != want:
            raise ValueError(
                f"planset fingerprint mismatch: metadata says {want}, "
                f"reloaded set hashes to {planset.fingerprint()} — the "
                f"bundle's artifact.json was edited or corrupted")
        cluster_model = cluster_model_from_dict(meta["cluster_model"])
        cluster_stats = {int(c): _coerce_stats(s)
                         for c, s in meta["cluster_stats"].items()}
        precision = planset.plan_for(planset.default)
        stats = cluster_stats.get(planset.default,
                                  cluster_stats[sorted(cluster_stats)[0]])
    else:
        precision = _precision_from_meta(meta)
        stats = _coerce_stats(meta["stats"])
    scheme = T.QuantScheme(**meta["scheme"])
    task = TaskSpec(**meta["task"]) if meta["task"] is not None else None
    target_name = meta["target"]["name"]
    n_out = int(meta["target"]["n_out"])
    dtype = jnp.dtype(meta["param_dtype"])
    tokenizer = None
    if meta.get("tokenizer"):
        from repro.data.tokenizer import WordPieceTokenizer
        tokenizer = WordPieceTokenizer(meta["tokenizer"]["vocab"],
                                       meta["tokenizer"]["granularity"])

    # Structure-only template: float-init + apply_plan with the SAVED
    # stats/plan yields the exact leaf layout that was saved, and
    # restore() only reads leaf shapes/dtypes — so trace it abstractly
    # (eval_shape): no weights are sampled, nothing is quantized.
    def build_template():
        kbase, khead = jax.random.split(jax.random.PRNGKey(0))
        float_precision = PrecisionPlan.full_float(cfg.num_layers,
                                                   precision.float_dtype)
        template = T.init_params(kbase, cfg, float_precision, dtype=dtype)
        head = get_target(target_name).init(khead, cfg, n_out, dtype)
        if head is not None:
            template["head"] = head
        if adaptive:
            # v3 stores the float tree itself; quantization happens below
            return template
        qtemplate, _ = ptq.apply_plan(template, cfg, precision, stats,
                                      scheme=scheme)
        return qtemplate

    qtemplate = jax.eval_shape(build_template)
    restored = store.restore(directory, 0, qtemplate)
    float_params = None
    if adaptive:
        # rebuild the default member's quantized tree; the same call per
        # member happens in Artifact.router() — identical inputs, so the
        # trees are bit-identical to the ones that were saved/served
        float_params = restored
        params, plan = ptq.apply_plan(float_params, cfg, precision, stats,
                                      scheme=scheme)
    else:
        params = restored
        plan = T.build_plan(cfg, precision)
    return Artifact(cfg=cfg, precision=precision, scheme=scheme, stats=stats,
                    params=params, plan=plan, task=task,
                    target_name=target_name, n_out=n_out, path=directory,
                    compute_dtype=meta.get("compute_dtype", "float32"),
                    tokenizer=tokenizer, planset=planset,
                    cluster_model=cluster_model, cluster_stats=cluster_stats,
                    float_params=float_params)
