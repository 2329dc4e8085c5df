"""repro.toolkit: registries, Pipeline parity, SAMP facade, artifacts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.core.precision import make_policy
from repro.data import eval_accuracy, get_batch
from repro.models import transformer as T
from repro.toolkit import (LATENCY_BACKENDS, SAMP, TARGETS, Pipeline,
                           TargetSpec, get_latency_backend, get_target,
                           load_artifact, register_target)
from repro.toolkit.latency import RooflineBackend, encoder_latency
from repro.toolkit.registry import Registry

KEY = jax.random.PRNGKey(0)


def tiny_cfg(num_layers=2):
    return get_config("bert-base").reduced().replace(num_layers=num_layers)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_builtin_targets_registered():
    assert {"cls", "pair_matching", "seq_labeling", "lm"} <= set(
        TARGETS.names())
    assert {"roofline", "wallclock"} <= set(LATENCY_BACKENDS.names())


def test_unknown_name_error_lists_available():
    with pytest.raises(KeyError, match="unknown target head 'nope'"):
        get_target("nope")
    with pytest.raises(KeyError, match="available"):
        get_latency_backend("nope")


def test_duplicate_registration_rejected():
    reg = Registry("thing")
    reg.register("a", 1)
    with pytest.raises(KeyError, match="already registered"):
        reg.register("a", 2)
    reg.register("a", 2, overwrite=True)
    assert reg.get("a") == 2


def test_custom_target_registration_and_use():
    """A mean-pool classifier registered by a user flows through the whole
    Pipeline (init -> forward -> predict)."""
    from repro.models import layers as L

    def mean_init(key, cfg, n_out, dtype):
        return {"out": L.init_linear(key, cfg.d_model, n_out, True, dtype)}

    def mean_apply(params, hidden, cfg):
        return L.dense(jnp.mean(hidden, axis=1), params["head"]["out"])

    spec = TargetSpec(name="mean_pool", init=mean_init, apply=mean_apply)
    register_target("mean_pool", spec, overwrite=True)

    cfg = tiny_cfg()
    pipe = Pipeline.build(cfg, "tnews", target="mean_pool", seq_len=16,
                          float_dtype="float32")
    pipe.init_params(KEY)
    pred = pipe.predict(get_batch(pipe.task, 0, 8, "dev"))
    assert pred.shape == (8,)
    assert pred.max() < pipe.task.n_classes


def test_registry_decorator_form():
    reg = Registry("gadget")

    @reg.register("g")
    def gadget():
        return 7

    assert reg.get("g")() == 7 and "g" in reg


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained():
    """A briefly fine-tuned 2-layer BERT facade (shared across tests)."""
    samp = SAMP.from_config(tiny_cfg(), task="tnews", seq_len=16,
                            float_dtype="float32")
    samp.finetune(steps=40, batch_size=16)
    return samp


def test_pipeline_eval_matches_hand_rolled_closure(trained):
    """Pipeline.predict/eval must be bit-identical to the old quickstart's
    hand-rolled T.forward + apply_head closure."""
    pipe = trained.pipeline
    cfg, params, plan = pipe.cfg, pipe.params, pipe.plan

    @jax.jit
    def f(tokens, segments):
        h, _ = T.forward(params, {"tokens": tokens, "segments": segments},
                         cfg, plan, compute_dtype=jnp.float32)
        return jnp.argmax(T.apply_head(h, params, "cls"), -1)

    def hand(b):
        return f(jnp.asarray(b["tokens"]), jnp.asarray(b["segments"]))

    b = get_batch(pipe.task, 0, 32, "dev")
    assert np.array_equal(np.asarray(hand(b)), pipe.predict(b))
    assert pipe.eval(batches=2, batch_size=32) == eval_accuracy(
        hand, pipe.task, batches=2, batch_size=32)


def test_pipeline_stages_compose_to_fused_forward(trained):
    """The staged decomposition (embedding -> encoder -> target) equals the
    substrate's fused forward."""
    pipe = trained.pipeline
    b = pipe._model_inputs(get_batch(pipe.task, 3, 4, "dev"))
    logits = pipe.forward(pipe.params, b)
    hidden, _ = T.forward(pipe.params, b, pipe.cfg, pipe.plan,
                          compute_dtype=jnp.float32)
    want = T.apply_head(hidden, pipe.params, "cls")
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))


def test_pipeline_lm_eval_and_predict():
    cfg = get_config("qwen2-0.5b").reduced()
    pipe = Pipeline.build(cfg, "lm", seq_len=16, float_dtype="float32")
    pipe.init_params(KEY)
    b = get_batch(pipe.task, 0, 4, "dev")
    assert pipe.predict(b).shape == (4, 16)
    acc = pipe.eval(batches=1, batch_size=4)
    assert 0.0 <= acc <= 1.0


def test_tokenizer_stage_round_trip():
    from repro.data import WordPieceTokenizer
    tok = WordPieceTokenizer.train(["hello world", "quantize the encoder"],
                                   vocab_size=64)
    cfg = tiny_cfg()
    pipe = Pipeline.build(cfg, "tnews", seq_len=16, float_dtype="float32",
                          tokenizer=tok)
    batch = pipe.tokenizer(["hello world", "the encoder"])
    assert batch["tokens"].shape == (2, 16)
    pairs = pipe.tokenizer([("hello", "world")])
    assert pairs["segments"].max() == 1


def test_pipeline_without_tokenizer_raises():
    pipe = Pipeline.build(tiny_cfg(), "tnews", seq_len=16)
    with pytest.raises(ValueError, match="without a tokenizer"):
        pipe.tokenizer(["some text"])


# ---------------------------------------------------------------------------
# latency backends
# ---------------------------------------------------------------------------


def test_roofline_backend_matches_function():
    cfg = get_config("bert-base")
    pol = make_policy(cfg, "ffn", "bfloat16")
    fn = RooflineBackend().bind(cfg, batch=8, seq=128)
    assert fn(None, None, pol) == encoder_latency(cfg, pol, batch=8, seq=128)


def test_roofline_int8_is_faster():
    cfg = get_config("bert-base")
    t_f = encoder_latency(cfg, PrecisionPlan.full_float(cfg.num_layers),
                          batch=8, seq=128)
    t_q = encoder_latency(cfg, make_policy(cfg, "full"), batch=8, seq=128)
    assert t_q < t_f


def test_wallclock_backend_runs(trained):
    pipe = trained.pipeline
    fn = get_latency_backend("wallclock")(reps=2, warmup=1).bind(
        pipe.cfg, batch=2, seq=8, compute_dtype=jnp.float32)
    t = fn(pipe.params, pipe.plan, pipe.policy)
    assert t > 0


# ---------------------------------------------------------------------------
# facade + artifacts
# ---------------------------------------------------------------------------


def test_autotune_and_artifact_round_trip(trained, tmp_path):
    bundle = str(tmp_path / "bundle")
    report = trained.autotune(stride=1, eval_batches=1, eval_batch_size=32,
                              save_to=bundle)
    assert report.chosen.mode_name == "quant_ffn_only"
    assert report.points[0].mode_name == "float"
    assert len({(p.mode_name, p.k) for p in report.points}) == \
        len(report.points)

    # -- reload: bit-identical predictions, no calibration batches ----------
    reloaded = SAMP.load(bundle)
    b = get_batch(trained.task, 5, 32, "dev")
    np.testing.assert_array_equal(trained.predict(b), reloaded.predict(b))

    art = load_artifact(bundle)
    assert art.policy == trained.quantized.policy
    assert art.target_name == "cls"
    # quantized leaves survived as int8
    leaves = jax.tree_util.tree_leaves(art.params)
    assert any(l.dtype == jnp.int8 for l in leaves)


def test_autotune_threshold_modes(trained):
    pts = trained.sweep(stride=1, eval_batches=1, eval_batch_size=32)
    base = pts[0].latency
    recs = trained.recommend(max_latency=base)          # everything feasible
    assert all(r.point.latency <= base for r in recs)
    recs = trained.recommend(min_accuracy=0.0)
    assert recs                                          # always satisfiable


def test_apply_named_policy(trained):
    pipe = trained.apply(make_policy(trained.cfg, "full", "float32"))
    assert pipe.policy.num_quant_mha == trained.cfg.num_layers
    assert pipe.predict(get_batch(trained.task, 0, 8, "dev")).shape == (8,)


def test_facade_requires_params():
    samp = SAMP.from_config(tiny_cfg(), task="tnews", seq_len=16,
                            float_dtype="float32")
    with pytest.raises(ValueError, match="no params"):
        samp.calibrate()
    with pytest.raises(ValueError, match="nothing to save"):
        samp.save("/tmp/nowhere")


def test_lm_artifact_serves(tmp_path):
    """The serve path: quantize an LM, bundle it, reload, generate."""
    from repro.serve import Request
    cfg = get_config("qwen2-0.5b").reduced()
    samp = SAMP.from_config(cfg, task="lm", seq_len=16,
                            float_dtype="float32")
    samp.pipeline.init_params(KEY)
    samp.calibrate(num_batches=2, batch_size=2)
    samp.apply(make_policy(cfg, "ffn", "float32"))
    bundle = str(tmp_path / "lm_bundle")
    samp.save(bundle)

    server = SAMP.load(bundle).serve(batch_slots=2, max_len=32)
    server.submit(Request(uid=0, prompt=[3, 5, 7], max_tokens=4))
    done = server.run()
    assert len(done) == 1 and len(done[0].output) == 4


def test_artifact_preserves_compute_dtype_and_tokenizer(tmp_path):
    """Round trip under the default bfloat16 config, with a tokenizer:
    compute dtype and text-input support must survive the bundle."""
    from repro.data import WordPieceTokenizer
    tok = WordPieceTokenizer.train(["hello world bundle"], vocab_size=64)
    cfg = tiny_cfg()
    samp = SAMP.from_config(cfg, task="tnews", seq_len=16, tokenizer=tok)
    assert samp.pipeline.compute_dtype == jnp.bfloat16
    samp.pipeline.init_params(KEY)
    samp.calibrate(num_batches=2, batch_size=4)
    samp.apply(make_policy(cfg, "ffn", "bfloat16"))
    bundle = str(tmp_path / "bf16_bundle")
    samp.save(bundle)

    reloaded = SAMP.load(bundle)
    assert reloaded.current.compute_dtype == jnp.bfloat16
    b = get_batch(samp.task, 0, 16, "dev")
    np.testing.assert_array_equal(samp.predict(b), reloaded.predict(b))
    # text path survives the round trip
    assert reloaded.current.predict_texts(["hello world"]).shape == (1,)


def test_finetune_invalidates_stale_state():
    """Re-finetuning must drop stats/points/quantized measured on the old
    weights; re-calibrating must drop old sweep points."""
    samp = SAMP.from_config(tiny_cfg(), task="tnews", seq_len=16,
                            float_dtype="float32")
    samp.finetune(steps=2, batch_size=8)
    samp.calibrate(num_batches=1, batch_size=4)
    samp.sweep(stride=2, eval_batches=1, eval_batch_size=8)
    samp.apply(make_policy(samp.cfg, "ffn", "float32"))
    assert samp.points is not None and samp.quantized is not None
    samp.finetune(steps=2, batch_size=8)
    assert samp.stats is None and samp.points is None \
        and samp.quantized is None
    samp.calibrate(num_batches=1, batch_size=4)
    samp.sweep(stride=2, eval_batches=1, eval_batch_size=8)
    samp.apply(make_policy(samp.cfg, "ffn", "float32"))
    samp.calibrate(num_batches=1, batch_size=4)
    assert samp.points is None and samp.quantized is None


def test_loaded_facade_is_deploy_only(trained, tmp_path):
    """A facade rebuilt from a bundle has no float model: the tuning
    workflow must refuse loudly instead of running on int8 params."""
    bundle = str(tmp_path / "deploy_bundle")
    trained.calibrate(num_batches=1, batch_size=4)
    trained.apply(make_policy(trained.cfg, "ffn", "float32"))
    trained.save(bundle)
    loaded = SAMP.load(bundle)
    for call in (loaded.calibrate, loaded.sweep, loaded.autotune,
                 loaded.finetune,
                 lambda: loaded.apply(make_policy(loaded.cfg, "ffn",
                                                  "float32"))):
        with pytest.raises(ValueError, match="deploy"):
            call()
    # ...but the deploy surface still works
    assert loaded.predict(get_batch(trained.task, 0, 8, "dev")).shape == (8,)


def test_autotune_rejects_unknown_prefer(trained):
    with pytest.raises(KeyError, match="matches no recommended mode"):
        trained.autotune(prefer="ffn", stride=1, eval_batches=1,
                         eval_batch_size=16)


def test_repro_top_level_export():
    import repro
    assert repro.SAMP is SAMP
    assert "SAMP" in dir(repro)
    with pytest.raises(AttributeError):
        repro.not_a_thing
