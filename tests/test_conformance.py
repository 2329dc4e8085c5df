"""Cross-architecture conformance suite: every registered config, the full
pipeline at reduced dims.

Each architecture runs build -> synthetic calibration -> apply_plan ->
fused-vs-reference forward parity -> artifact bundle round-trip. The
parameterization is derived from the registry itself (``all_configs()``),
with ``<family>__<arch>`` test ids so CI's conformance matrix selects one
family per leg (``-k "<family>__"``). MoE configs additionally quantize
through the schema-v4 ``experts`` block family (per-expert weight scales,
float router).

No silent skips: every config must pass every stage. An architecture that
genuinely cannot run a stage must carry an explicit xfail/skip marker with
a reason in ``_STAGE_MARKS`` — ``test_registry_fully_covered`` fails if
the parameter list and the registry ever drift apart.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, get_config
from repro.core.calibration import synthetic_calibration_batches
from repro.core.precision import make_policy
from repro.core.samp import SAMPEngine, moe_family_variant
from repro.kernels.backend import get_backend
from repro.models import transformer as T
from repro.quant import ptq
from repro.toolkit.artifact import load_artifact, save_artifact

KEY = jax.random.PRNGKey(0)
ARCHS = sorted(all_configs())

# arch -> {stage: pytest.mark}: the ONLY sanctioned way to exempt an
# architecture from a stage. Every entry needs a reason= — an empty dict
# means the whole registry conforms end to end.
_STAGE_MARKS: dict = {}


def _params_for(arch, stage):
    marks = _STAGE_MARKS.get(arch, {})
    return pytest.param(arch, id=f"{get_config(arch).family}__{arch}",
                        marks=marks.get(stage, ()))


def _stage_params(stage):
    return [_params_for(a, stage) for a in ARCHS]


_built: dict = {}


def built(arch):
    """Build-once cache: float init + calibration + quantized apply for one
    reduced config, shared by every stage of that arch's conformance run."""
    if arch not in _built:
        cfg = get_config(arch).reduced()
        eng = SAMPEngine(cfg, float_dtype="float32")
        params = T.init_params(KEY, cfg, eng.float_precision)
        batches = synthetic_calibration_batches(cfg, num_batches=2,
                                                seq_len=16)
        precision = make_policy(cfg, "ffn", float_dtype="float32")
        if cfg.moe is not None:
            precision = moe_family_variant(precision)
        stats = eng.calibrate(params, batches, precision=precision)
        qparams, qplan = eng.apply(params, stats, precision)
        _built[arch] = (cfg, eng, precision, stats, qparams, qplan,
                        batches[0])
    return _built[arch]


def _forward(cfg, params, plan, batch, backend=None):
    out, _ = T.forward(params, batch, cfg, plan, compute_dtype=jnp.float32,
                       backend=backend)
    return np.asarray(out)


def test_registry_fully_covered():
    """The suite's parameter list IS the registry — a new config shows up
    here automatically, and hand-pruning one fails loudly."""
    assert ARCHS == sorted(all_configs()) and len(ARCHS) >= 11
    for arch, stages in _STAGE_MARKS.items():
        assert arch in ARCHS, f"_STAGE_MARKS names unknown arch {arch!r}"
        assert stages, f"_STAGE_MARKS[{arch!r}] must not be empty"


@pytest.mark.parametrize("arch", _stage_params("apply"))
def test_calibrate_and_apply(arch):
    """Synthetic calibration + apply_plan produce a quantized tree whose
    quantized leaf count matches the plan; MoE archs get per-expert
    (E, 1, F) weight-scale leaves under the v4 ``experts`` family."""
    cfg, eng, precision, stats, qparams, qplan, batch = built(arch)
    assert precision.num_quant_ffn == cfg.num_layers
    leaves = jax.tree_util.tree_leaves_with_path(qparams)
    int8 = [jax.tree_util.keystr(p) for p, v in leaves
            if hasattr(v, "dtype") and v.dtype == jnp.int8]
    assert int8, f"{arch}: no int8 leaves after apply_plan"
    if cfg.moe is not None:
        E = cfg.moe.held         # stacks hold the layer's held experts
        expert_scales = [
            (p, v) for p, v in leaves
            if "ffn" in jax.tree_util.keystr(p)
            and jax.tree_util.keystr(p).endswith(".scale")
            and getattr(v, "ndim", 0) >= 3 and v.shape[-3] == E
            and v.shape[-2] == 1]
        assert expert_scales, (f"{arch}: experts family produced no "
                               f"per-expert (E, 1, F) scale leaves")
        # the router projection must stay a plain float leaf
        routers = [v for p, v in leaves
                   if "router" in jax.tree_util.keystr(p)]
        assert routers and all(
            jnp.issubdtype(v.dtype, jnp.floating) for v in routers)


@pytest.mark.parametrize("arch", _stage_params("parity"))
def test_fused_matches_reference(arch):
    """The fused Pallas backend (interpret mode) matches the reference XLA
    substrate on the quantized forward — same tolerance as the dedicated
    backend suite (tests/test_backend.py)."""
    cfg, eng, precision, stats, qparams, qplan, batch = built(arch)
    ref = _forward(cfg, qparams, qplan, batch)
    fused = _forward(cfg, qparams, qplan, batch, get_backend("fused"))
    rel = float(np.abs(ref - fused).max() / (np.abs(ref).max() + 1e-9))
    assert rel < 5e-3, f"{arch}: fused-vs-reference rel Linf {rel}"


@pytest.mark.parametrize("arch", _stage_params("bundle"))
def test_bundle_roundtrip(arch, tmp_path):
    """save_artifact -> load_artifact reproduces the plan fingerprint and a
    bit-identical forward — v4 experts-family plans round-trip through the
    bundle metadata like any other schema version."""
    cfg, eng, precision, stats, qparams, qplan, batch = built(arch)
    path = save_artifact(str(tmp_path / "bundle"), cfg=cfg,
                         policy=precision, stats=stats, params=qparams,
                         scheme=eng.scheme)
    art = load_artifact(path)
    assert art.precision.fingerprint() == precision.fingerprint()
    assert art.cfg == cfg
    want = _forward(cfg, qparams, qplan, batch)
    got = _forward(art.cfg, art.params, art.plan, batch)
    np.testing.assert_array_equal(want, got)
