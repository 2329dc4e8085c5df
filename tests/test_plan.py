"""PrecisionPlan: schema validation, serialization round-trips, the named
prefix plans, search strategies, per-block PTQ, and the plan-keyed runtime
cache."""
import json
import os
import pathlib
import subprocess
import sys
import types

from _hypothesis_shim import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import (ACT_SCHEMES, BLOCKS, FLOAT_LAYER, LayerMode,
                             LayerPlan, PrecisionPlan, QuantSpec,
                             WEIGHT_SCHEMES)
from repro.core.precision import make_policy, paper_grid
from repro.core.quantize import QuantizedTensor
from repro.core.samp import SAMPEngine, SEARCH_STRATEGIES, get_strategy
from repro.models import transformer as T
from repro.quant import ptq
from repro.toolkit import SAMP, Pipeline
from repro.toolkit.plan_lint import lint

KEY = jax.random.PRNGKey(0)
REPO = pathlib.Path(__file__).resolve().parents[1]
BENCH = REPO / "bench"
DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA, "golden_plan.json")
GOLDEN_FINGERPRINT = \
    "b21e3181d2b5852aa897fbc6414f6a28f5cf1841f9743cf49b69fd3820e88e7b"

settings = hypothesis.settings(max_examples=30, deadline=None)

INT8 = QuantSpec("int8_per_channel", "int8_per_tensor")


# ---------------------------------------------------------------------------
# schema validation
# ---------------------------------------------------------------------------


def test_quantspec_validates_schemes():
    with pytest.raises(ValueError, match="weight scheme"):
        QuantSpec(weight="int4", act="int8_per_tensor")
    with pytest.raises(ValueError, match="act scheme"):
        QuantSpec(weight="int8_per_channel", act="fp8")
    with pytest.raises(ValueError, match="float or W8A8"):
        QuantSpec(weight="int8_per_channel", act="float")
    with pytest.raises(ValueError, match="float or W8A8"):
        QuantSpec(weight="float", act="int8_per_tensor")
    with pytest.raises(ValueError, match="unknown calibrator"):
        QuantSpec(weight="int8_per_channel", act="int8_per_tensor",
                  calibrator="magic")


def test_layerplan_block_lookup_and_mode():
    lp = LayerPlan(ffn_in=INT8, ffn_out=INT8)
    assert lp.spec("ffn_in").quantized and not lp.spec("qkv").quantized
    assert lp.mode is LayerMode.QUANT_FFN_ONLY
    assert LayerPlan(qkv=INT8).mode is LayerMode.FULLY_QUANT
    assert FLOAT_LAYER.mode is LayerMode.FLOAT
    # "router" became a schema-v4 block family (resolves, stays float) —
    # only genuinely unknown names raise
    assert not lp.spec("router").quantized
    with pytest.raises(KeyError, match="unknown block"):
        lp.spec("bogus")


def test_plan_rejects_bad_inputs():
    with pytest.raises(ValueError, match="float_dtype"):
        PrecisionPlan((FLOAT_LAYER,), "int8")
    with pytest.raises(ValueError, match="schema_version"):
        PrecisionPlan.from_dict({"layers": [{}]})
    # "router" is a v4 family now: under a v1 header it is rejected as a
    # version violation, and truly unknown keys still fail as unknown
    with pytest.raises(ValueError, match="schema v4"):
        PrecisionPlan.from_dict({"schema_version": 1,
                                 "layers": [{"router": {}}]})
    with pytest.raises(ValueError, match="unknown blocks"):
        PrecisionPlan.from_dict({"schema_version": 1,
                                 "layers": [{"bogus": {}}]})
    with pytest.raises(ValueError, match="non-empty"):
        PrecisionPlan.from_dict({"schema_version": 1, "layers": []})
    # typoed top-level keys must fail loudly, not fall back to defaults
    with pytest.raises(ValueError, match="unknown plan fields"):
        PrecisionPlan.from_dict({"schema_version": 1,
                                 "float_dtypes": "float32",
                                 "layers": [{}]})


# ---------------------------------------------------------------------------
# round trips (property-based via the hypothesis shim)
# ---------------------------------------------------------------------------


def _spec_strategy():
    quant = st.tuples(st.sampled_from(WEIGHT_SCHEMES[1:]),
                      st.sampled_from(ACT_SCHEMES[1:]),
                      st.sampled_from(("minmax", "percentile", "mse",
                                       "entropy"))
                      ).map(lambda t: QuantSpec(*t))
    return st.one_of(st.just(QuantSpec()), quant)


def _plan_strategy():
    layer = st.builds(LayerPlan, qkv=_spec_strategy(),
                      attn_out=_spec_strategy(), ffn_in=_spec_strategy(),
                      ffn_out=_spec_strategy())
    return st.builds(PrecisionPlan,
                     layers=st.lists(layer, min_size=1, max_size=8)
                     .map(tuple),
                     float_dtype=st.sampled_from(("float32", "bfloat16")))


@settings
@hypothesis.given(_plan_strategy())
def test_json_round_trip_preserves_fingerprint(plan):
    reloaded = PrecisionPlan.from_json(plan.to_json())
    assert reloaded == plan
    assert reloaded.fingerprint() == plan.fingerprint()
    # canonical form is insensitive to key order / whitespace
    shuffled = json.dumps(json.loads(plan.to_json()), indent=4)
    assert PrecisionPlan.from_json(shuffled).fingerprint() == \
        plan.fingerprint()


@settings
@hypothesis.given(st.integers(1, 24), st.integers(0, 24),
                  st.sampled_from((LayerMode.QUANT_FFN_ONLY,
                                   LayerMode.FULLY_QUANT)))
def test_policy_shim_equivalence(n, k, mode):
    """A named policy ('ffnK'/'fullK') is the prefix plan: the first k
    layers in ``mode``, the rest float, with matching counts, a fingerprint
    that only depends on the content, and one scan group per run."""
    k = min(k, n)
    plan = PrecisionPlan.prefix(n, k, mode, "float32")
    name = ("ffn" if mode is LayerMode.QUANT_FFN_ONLY else "full") + str(k)
    assert make_policy(types.SimpleNamespace(num_layers=n), name,
                       "float32") == plan
    assert plan.modes == (mode,) * k + (LayerMode.FLOAT,) * (n - k)
    assert plan.num_quant_ffn == k
    assert plan.num_quant_mha == (k if mode.quant_mha else 0)
    assert plan.fingerprint() == \
        PrecisionPlan.prefix(n, k, mode, "float32").fingerprint()
    assert plan.group_boundaries() == [
        run for run in ((0, k, mode), (k, n, LayerMode.FLOAT))
        if run[0] < run[1]]


# each benchmark cell's plan as bench/system.precision_plan builds it, by
# fingerprint: the serving runtime keys the cell's executables on it, so a
# refactor of the precision API must leave these unchanged
CELL_FINGERPRINTS = {
    "bert-base-samp":
        "000fbb9e3eb83a78909090cb063685a43a4e98eb46b9cf336b7e002bb0038400",
    "qwen2-0.5b-samp":
        "3a6273acea8007155b1eaf9125726db7806bb5ad68f5c025490056a8e625c634",
    "deepseek-v2-lite-ep4-samp":
        "6e7389919d67572646c426f01a6848563024d9a4c609baf6a6cf74ac49ce8167",
}


@pytest.mark.parametrize(
    "name", sorted(p.stem for p in (BENCH / "configs").glob("*.json")))
def test_cell_plans_keep_their_fingerprints(name):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import system
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    plan = system.precision_plan(config, system.arch_config(config))
    assert plan.fingerprint() == CELL_FINGERPRINTS[name]


def test_file_round_trip(tmp_path):
    plan = PrecisionPlan.prefix(6, 3, LayerMode.FULLY_QUANT, "float32",
                                calibrator="percentile")
    path = str(tmp_path / "plan.json")
    plan.save(path)
    assert PrecisionPlan.load(path).fingerprint() == plan.fingerprint()


# ---------------------------------------------------------------------------
# the golden file guards the on-disk schema
# ---------------------------------------------------------------------------


def test_golden_plan_schema_and_fingerprint():
    """If this fails after an intentional schema change, bump SCHEMA_VERSION
    and regenerate the golden (old plan files in the wild must keep
    loading or fail loudly — silent reinterpretation is the bug)."""
    plan = PrecisionPlan.load(GOLDEN)
    assert plan.fingerprint() == GOLDEN_FINGERPRINT
    assert plan.num_layers == 4
    assert plan.layers[0].attn_out.calibrator == "percentile"
    assert plan.layers[1].ffn_in.act == "int8_per_token"
    assert plan.layers[3].qkv.weight == "int8_per_tensor"
    assert plan.modes == (LayerMode.FULLY_QUANT, LayerMode.QUANT_FFN_ONLY,
                          LayerMode.FLOAT, LayerMode.FULLY_QUANT)


def test_plan_lint_accepts_golden_and_rejects_garbage(tmp_path):
    lint(GOLDEN, num_layers=4, log=lambda *_: None)
    with pytest.raises(ValueError, match="layers"):
        lint(GOLDEN, num_layers=12, log=lambda *_: None)
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ValueError, match="JSON"):
        lint(str(bad), log=lambda *_: None)
    bad.write_text(json.dumps({"schema_version": 1,
                               "layers": [{"qkv": {"weight": "int4",
                                                   "act": "float"}}]}))
    with pytest.raises(ValueError, match="schema violation"):
        lint(str(bad), log=lambda *_: None)


@pytest.mark.slow
def test_plan_lint_cli_exit_codes(tmp_path):
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ok = subprocess.run(
        [sys.executable, "-m", "repro.toolkit.plan_lint", GOLDEN,
         "--layers", "4"], cwd=root, env=env, capture_output=True)
    assert ok.returncode == 0, ok.stderr
    bad = subprocess.run(
        [sys.executable, "-m", "repro.toolkit.plan_lint", GOLDEN,
         "--layers", "7"], cwd=root, env=env, capture_output=True)
    assert bad.returncode == 1


# ---------------------------------------------------------------------------
# paper_grid dedupe (satellite)
# ---------------------------------------------------------------------------


def test_paper_grid_has_no_duplicate_policies():
    for stride in (1, 2, 3):
        grid = paper_grid(12, stride=stride)
        policies = [g[2].modes for g in grid]
        assert len(policies) == len(set(policies))
        # exactly one float baseline, always first
        assert grid[0][0] == "float"
        assert sum(1 for g in grid if g[2].num_quant_ffn == 0
                   and g[2].num_quant_mha == 0) == 1


# ---------------------------------------------------------------------------
# per-block PTQ + end-to-end
# ---------------------------------------------------------------------------


def _setup(arch="qwen2-0.5b"):
    cfg = get_config(arch).reduced()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)
    batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (2, 16),
                                             0, cfg.vocab_size)}
               for i in range(2)]
    return cfg, eng, params, batches


def test_per_block_plan_quantizes_only_named_blocks():
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    layer = LayerPlan(qkv=INT8, ffn_out=INT8)     # attn_out/ffn_in float
    plan = PrecisionPlan.uniform(cfg.num_layers, layer, "float32")
    qp, eplan = eng.apply(params, stats, plan)
    for lp in T.unpack_layers(qp, eplan):
        assert isinstance(lp["attn"]["wq"]["w"], QuantizedTensor)
        assert isinstance(lp["ffn"]["wd"]["w"], QuantizedTensor)
        assert not isinstance(lp["attn"]["wo"]["w"], QuantizedTensor)
        assert not isinstance(lp["ffn"]["wg"]["w"], QuantizedTensor)
        assert "p_scale" in lp["attn"]            # qkv static => bmm scales
    out, _ = T.forward(qp, batches[0], cfg, eplan, compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_attn_out_only_plan_keeps_bmm_float():
    """The attention score/value bmms belong to the qkv block: a plan
    quantizing only attn_out must leave them float — no bmm scales, and
    the execution plan's quant_bmm gate off — so the declared-float
    softmax path never runs int8."""
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    plan = PrecisionPlan.uniform(cfg.num_layers, LayerPlan(attn_out=INT8),
                                 "float32")
    assert not plan.bmm_quantized(0)
    qp, eplan = eng.apply(params, stats, plan)
    assert all(g.quant_bmm is False for g in eplan)
    lp = T.unpack_layers(qp, eplan)[0]
    assert isinstance(lp["attn"]["wo"]["w"], QuantizedTensor)
    assert "p_scale" not in lp["attn"]
    # ...and the forward matches the float bmm path bit-for-bit except for
    # the quantized wo projection: compare against a hand-built reference
    # where ONLY wo is swapped
    out, _ = T.forward(qp, batches[0], cfg, eplan, compute_dtype=jnp.float32)
    ref, _ = T.forward(params, batches[0], cfg, eng.float_plan,
                       compute_dtype=jnp.float32)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    full = PrecisionPlan.uniform(cfg.num_layers,
                                 LayerPlan(qkv=INT8, attn_out=INT8),
                                 "float32")
    qp2, eplan2 = eng.apply(params, stats, full)
    assert all(g.quant_bmm for g in eplan2)
    out2, _ = T.forward(qp2, batches[0], cfg, eplan2,
                        compute_dtype=jnp.float32)
    rel2 = float(jnp.max(jnp.abs(out2 - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < rel2            # float bmms => strictly less quant error


def test_mixed_calibrator_families_accept_shared_kwargs():
    """One capture run over a plan mixing percentile and mse calibrators
    must route percentile= only to the percentile constructor."""
    cfg, eng, params, batches = _setup("bert-base")
    layer = LayerPlan(
        ffn_in=QuantSpec("int8_per_channel", "int8_per_tensor",
                         "percentile"),
        ffn_out=QuantSpec("int8_per_channel", "int8_per_tensor", "mse"))
    plan = PrecisionPlan.uniform(cfg.num_layers, layer, "float32")
    stats = eng.calibrate(params, batches, precision=plan, percentile=99.0)
    assert all("ffn_in" in s and "ffn_hidden" in s for s in stats.values())


def test_per_tensor_weight_scheme_scale_shape():
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    spec = QuantSpec("int8_per_tensor", "int8_per_tensor")
    plan = PrecisionPlan.uniform(cfg.num_layers, LayerPlan(ffn_in=spec,
                                                           ffn_out=spec),
                                 "float32")
    qp, eplan = eng.apply(params, stats, plan)
    wg = T.unpack_layers(qp, eplan)[0]["ffn"]["wg"]["w"]
    assert isinstance(wg, QuantizedTensor)
    assert wg.scale.shape == (1,) * wg.values.ndim
    assert int(np.prod(wg.scale.shape)) == 1


def test_dynamic_act_blocks_store_no_xs():
    cfg, eng, params, _ = _setup()
    spec = QuantSpec("int8_per_channel", "int8_per_token")
    plan = PrecisionPlan.uniform(cfg.num_layers, LayerPlan(ffn_in=spec,
                                                           ffn_out=spec),
                                 "float32")
    qp, eplan = eng.apply(params, {}, plan)      # dynamic: no stats needed
    lp = T.unpack_layers(qp, eplan)[0]
    assert isinstance(lp["ffn"]["wg"]["w"], QuantizedTensor)
    assert "xs" not in lp["ffn"]["wg"]


def test_mixed_block_plan_groups_split_structurally():
    """Layers whose LayerPlans differ (static vs dynamic acts) must not
    stack into one scan group — their param trees differ structurally."""
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    static = LayerPlan(ffn_in=INT8, ffn_out=INT8)
    dyn_spec = QuantSpec("int8_per_channel", "int8_per_token")
    dynamic = LayerPlan(ffn_in=dyn_spec, ffn_out=dyn_spec)
    n = cfg.num_layers
    plan = PrecisionPlan((static,) * (n // 2) + (dynamic,) * (n - n // 2),
                         "float32")
    qp, eplan = eng.apply(params, stats, plan)
    assert len(eplan) == 2
    out, _ = T.forward(qp, batches[0], cfg, eplan, compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_per_block_calibrator_threading():
    """A plan naming percentile for ffn_in must produce a (clipped) amax no
    larger than the minmax amax on that site, leaving others at minmax."""
    cfg, eng, params, batches = _setup("bert-base")
    minmax = eng.calibrate(params, batches)
    layer = LayerPlan(ffn_in=QuantSpec("int8_per_channel",
                                       "int8_per_tensor", "percentile"))
    plan = PrecisionPlan.uniform(cfg.num_layers, layer, "float32")
    stats = eng.calibrate(params, batches, precision=plan, percentile=99.0)
    for lk in minmax:
        assert stats[lk]["ffn_in"] <= minmax[lk]["ffn_in"] + 1e-6
        assert stats[lk]["attn_in"] == pytest.approx(minmax[lk]["attn_in"])


def test_capture_stats_global_calibrator_override():
    cfg, eng, params, batches = _setup("bert-base")
    minmax = eng.calibrate(params, batches)
    clipped = eng.calibrate(params, batches, calibrator="percentile",
                            percentile=95.0)
    sites = 0
    for lk in minmax:
        for site in ("attn_in", "attn_out", "ffn_in", "ffn_hidden"):
            assert clipped[lk][site] <= minmax[lk][site] + 1e-6
            sites += clipped[lk][site] < minmax[lk][site] - 1e-9
    assert sites > 0                     # percentile actually clipped


# ---------------------------------------------------------------------------
# search strategies
# ---------------------------------------------------------------------------


def _proxy_fns(cfg, eng, params, batches):
    ref, _ = T.forward(params, batches[0], cfg, eng.float_plan,
                       compute_dtype=jnp.float32)

    def eval_fn(qp, plan, pol):
        out, _ = T.forward(qp, batches[0], cfg, plan,
                           compute_dtype=jnp.float32)
        return 1.0 - float(jnp.mean(jnp.abs(out - ref))
                           / (jnp.mean(jnp.abs(ref)) + 1e-9))

    def latency_fn(qp, plan, pol):
        return 1.0 - 0.02 * pol.num_quant_ffn - 0.01 * pol.num_quant_mha
    return eval_fn, latency_fn


def test_strategy_registry():
    assert {"prefix_grid", "greedy", "latency_budget"} <= \
        set(SEARCH_STRATEGIES)
    with pytest.raises(KeyError, match="unknown search strategy"):
        get_strategy("quantum_annealing")


def test_prefix_grid_strategy_emits_plans():
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    eval_fn, latency_fn = _proxy_fns(cfg, eng, params, batches)
    pts = eng.search("prefix_grid", params, stats, eval_fn, latency_fn,
                     stride=2)
    assert pts[0].mode_name == "float"
    assert all(isinstance(p.plan, PrecisionPlan) for p in pts)
    assert len({p.plan.fingerprint() for p in pts}) == len(pts)


def test_greedy_strategy_emits_subset_plans():
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    eval_fn, latency_fn = _proxy_fns(cfg, eng, params, batches)
    pts = eng.search("greedy", params, stats, eval_fn, latency_fn)
    assert pts[0].mode_name == "float"
    greedy = [p for p in pts if p.mode_name == "greedy"]
    assert [p.k for p in greedy] == list(range(1, cfg.num_layers + 1))
    # subsets are nested: each step adds one layer
    prev = set()
    for p in greedy:
        quant = {i for i, lp in enumerate(p.plan.layers) if lp.quant_ffn}
        assert prev < quant and len(quant) == p.k
        prev = quant
    recs = eng.recommend(pts)
    assert [r.mode_name for r in recs] == ["greedy"]


def test_latency_budget_strategy_respects_ceiling():
    cfg, eng, params, batches = _setup()
    stats = eng.calibrate(params, batches)
    eval_fn, latency_fn = _proxy_fns(cfg, eng, params, batches)
    budget = 0.95                                   # only deep-k feasible
    pts = eng.search("latency_budget", params, stats, eval_fn, latency_fn,
                     max_latency=budget)
    assert pts[0].mode_name == "float"
    assert all(p.latency <= budget for p in pts if p.mode_name != "float")
    assert len(pts) < len(paper_grid(cfg.num_layers))


# ---------------------------------------------------------------------------
# acceptance: autotune(strategy=...) -> plan survives save -> load -> serve
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tuned_facade():
    cfg = get_config("bert-base").reduced().replace(num_layers=2)
    samp = SAMP.from_config(cfg, task="tnews", seq_len=16,
                            float_dtype="float32")
    samp.finetune(steps=20, batch_size=16)
    return samp


@pytest.mark.parametrize("strategy", ["prefix_grid", "greedy"])
def test_autotune_strategies_return_plan_surviving_round_trip(
        tuned_facade, tmp_path, strategy):
    samp = tuned_facade
    samp.points = None                    # force a fresh search per strategy
    bundle = str(tmp_path / f"bundle_{strategy}")
    report = samp.autotune(strategy=strategy, eval_batches=1,
                           eval_batch_size=16, save_to=bundle)
    plan = report.plan
    assert isinstance(plan, PrecisionPlan)

    # plan file round trip: byte-identical fingerprint
    plan_path = str(tmp_path / f"{strategy}.json")
    plan.save(plan_path)
    assert PrecisionPlan.load(plan_path).fingerprint() == plan.fingerprint()

    # artifact round trip: same plan, same fingerprint, then serve
    from repro.data import get_batch
    from repro.serve import EncoderRequest
    reloaded = SAMP.load(bundle)
    assert reloaded.current.precision.fingerprint() == plan.fingerprint()
    server = reloaded.serve(batch_slots=4, max_len=16)
    b = get_batch(samp.task, 0, 4, "dev")
    for i in range(4):
        server.submit(EncoderRequest(
            uid=i, tokens=[int(t) for t in b["tokens"][i]],
            segments=[int(s) for s in b["segments"][i]]))
    done = {r.uid: r for r in server.run()}
    want = reloaded.predict(b)
    got = np.asarray([int(done[i].prediction) for i in range(4)])
    np.testing.assert_array_equal(got, want)


def test_shared_runtime_across_plans_compiles_once_per_bucket():
    """Acceptance: two pipelines under DIFFERENT plans sharing one runtime
    still prove <= 1 compile per (plan, bucket) via the trace counters."""
    from repro.data import get_batch
    cfg = get_config("bert-base").reduced().replace(num_layers=2)
    samp = SAMP.from_config(cfg, task="tnews", seq_len=16,
                            float_dtype="float32")
    samp.pipeline.init_params(KEY)
    samp.calibrate(num_batches=1, batch_size=4)
    qpipe = samp.apply(PrecisionPlan.prefix(cfg.num_layers, cfg.num_layers,
                                            LayerMode.QUANT_FFN_ONLY,
                                            "float32"))
    rt = samp.pipeline.runtime
    assert qpipe.runtime._exe is rt._exe          # one shared cache
    b = get_batch(samp.task, 0, 8, "dev")
    for _ in range(2):                            # second pass must be free
        samp.pipeline.predict(b)
        qpipe.predict(b)
    s = rt.stats
    assert s["traces"] == s["executables"] == 2   # one per plan, same bucket
    assert len(s["buckets"]) == 1                 # same (kind, B, S) bucket


def test_runtime_plan_keys_separate_same_structure_plans():
    """Two quantized plans with identical param structure but different
    fingerprints must not collide in a shared cache."""
    cfg = get_config("bert-base").reduced().replace(num_layers=2)
    pipe = Pipeline.build(cfg, "tnews", seq_len=16, float_dtype="float32")
    pipe.init_params(KEY)
    eng = SAMPEngine(cfg, float_dtype="float32")
    from repro.data import get_batch
    b = {k: jnp.asarray(v) for k, v in get_batch(pipe.task, 0, 4).items()
         if k in ("tokens", "segments")}
    stats = eng.calibrate(pipe.params, [b])
    p1 = PrecisionPlan.subset(2, [0], LayerMode.QUANT_FFN_ONLY, "float32")
    p2 = PrecisionPlan.subset(2, [1], LayerMode.QUANT_FFN_ONLY, "float32")
    assert p1.fingerprint() != p2.fingerprint()
    pipes = []
    for p in (p1, p2):
        qp, eplan = eng.apply(pipe.params, stats, p)
        pipes.append(pipe.with_policy(qp, eplan, p))
    for q in pipes:
        q.predict(b)
        q.predict(b)
    s = pipe.runtime.stats
    assert s["traces"] == s["executables"] == 2
