"""Serving runtime: bucketed executable cache, pad-mask correctness, batch
invariance, micro-batch scheduling, and the SAMP.serve() dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.core.precision import make_policy
from repro.data import get_batch
from repro.models import transformer as T
from repro.serve import (EncoderRequest, EncoderServeEngine, MicroBatcher,
                         Request, Runtime, ServeEngine, bucket_size)
from repro.toolkit import SAMP, Pipeline

KEY = jax.random.PRNGKey(0)


def tiny_bert(num_layers=2):
    return get_config("bert-base").reduced().replace(num_layers=num_layers)


@pytest.fixture(scope="module")
def bert_pipe():
    pipe = Pipeline.build(tiny_bert(), "tnews", seq_len=16,
                          float_dtype="float32")
    pipe.init_params(KEY)
    return pipe


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = get_config("qwen2-0.5b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    return cfg, params, plan


# ---------------------------------------------------------------------------
# bucketing + scheduler units
# ---------------------------------------------------------------------------


def test_bucket_size():
    assert [bucket_size(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert bucket_size(3, floor=8) == 8
    assert bucket_size(9, floor=8, cap=12) == 12      # cap can hold n
    assert bucket_size(20, floor=8, cap=12) == 32     # cap too small: ignored
    with pytest.raises(ValueError):
        bucket_size(0)


def test_microbatcher_max_wait_expiry_flushes_partial_bucket():
    """A bucket that never fills must still flush once its HEAD request has
    waited max_wait — the flushed batch is smaller than max_batch, and
    younger requests in other buckets stay queued."""
    mb = MicroBatcher(max_batch=4, max_wait=1.0, min_len=8)
    a = EncoderRequest(uid=0, tokens=[1] * 5)       # bucket 8
    b = EncoderRequest(uid=1, tokens=[1] * 6)       # bucket 8
    c = EncoderRequest(uid=2, tokens=[1] * 12)      # bucket 16, younger
    mb.submit(a, now=0.0)
    mb.submit(b, now=0.4)
    mb.submit(c, now=0.9)
    assert mb.ready(now=0.5) == []                  # nobody full or stale
    got = mb.ready(now=1.0)                         # a expired: partial flush
    assert [(blen, [q.uid for q in reqs]) for blen, reqs in got] == \
        [(8, [0, 1])]                               # b rides a's flush
    assert len(mb) == 1                             # c still waiting
    assert mb.ready(now=1.5) == []                  # c not yet stale
    got = mb.ready(now=2.0)
    assert [q.uid for _, reqs in got for q in reqs] == [2]


def test_microbatcher_force_drain_caps_batches_at_max_batch():
    """Drain pops everything, but never emits a batch above max_batch."""
    mb = MicroBatcher(max_batch=2, max_wait=100.0, min_len=8)
    for i in range(5):
        mb.submit(EncoderRequest(uid=i, tokens=[1] * 4), now=0.0)
    got = mb.ready(now=0.0, force=True)
    assert [[q.uid for q in reqs] for _, reqs in got] == [[0, 1], [2, 3], [4]]
    assert len(mb) == 0


def test_engine_shutdown_drains_partial_queues(bert_pipe):
    """run() (shutdown/synchronous drain) must retire every queued request
    even when no bucket is full or stale — and leave the queues empty."""
    pipe = bert_pipe
    eng = EncoderServeEngine(pipe.cfg, pipe.params, pipe.plan,
                             target=pipe.target.spec,
                             compute_dtype=jnp.float32,
                             max_batch=8, max_wait=1e9)
    rng = np.random.default_rng(5)
    for i in range(3):                  # three buckets, none full
        eng.submit(EncoderRequest(
            uid=i,
            tokens=rng.integers(1, pipe.cfg.vocab_size,
                                size=3 + 5 * i).tolist()), now=0.0)
    assert eng.step(now=0.0) == []      # nothing due yet
    done = eng.run(now=0.0)             # shutdown drain
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert all(r.done and r.logits is not None for r in done)
    assert len(eng.batcher) == 0
    assert eng.stats["retired"] == 3


def test_microbatcher_flush_rules():
    mb = MicroBatcher(max_batch=2, max_wait=10.0, min_len=8)
    r = [EncoderRequest(uid=i, tokens=[1] * (4 + i)) for i in range(5)]
    for i in range(3):
        mb.submit(r[i], now=0.0)            # bucket 8: one full batch + 1
    got = mb.ready(now=0.1)                 # full batch due, leftover waits
    assert [(b, [q.uid for q in reqs]) for b, reqs in got] == [(8, [0, 1])]
    assert len(mb) == 1
    assert mb.ready(now=0.1) == []          # not full, not stale
    mb.submit(r[3], now=5.0)
    got = mb.ready(now=11.0)                # max-wait flush (head is stale)
    assert [q.uid for _, reqs in got for q in reqs] == [2, 3]
    mb.submit(r[4], now=0.0)
    got = mb.ready(now=0.0, force=True)     # drain
    assert [q.uid for _, reqs in got for q in reqs] == [4]
    assert len(mb) == 0


# ---------------------------------------------------------------------------
# pad-mask correctness
# ---------------------------------------------------------------------------


def test_padded_forward_matches_natural_shape(bert_pipe):
    """A ragged batch padded to its bucket must produce the same logits as
    each row run at its natural length (band_mask drops pad keys)."""
    pipe = bert_pipe
    rng = np.random.default_rng(3)
    lengths = [5, 11, 16]
    tokens = [rng.integers(1, pipe.cfg.vocab_size, size=n) for n in lengths]
    rt = pipe.runtime
    B = len(lengths)
    padded = np.zeros((B, 16), np.int32)
    for i, t in enumerate(tokens):
        padded[i, :len(t)] = t
    got = rt.encode(pipe.params, {"tokens": padded,
                                  "segments": np.zeros((B, 16), np.int32)},
                    lengths=np.asarray(lengths))
    for i, t in enumerate(tokens):
        h, _ = T.forward(pipe.params,
                         {"tokens": jnp.asarray(t)[None],
                          "segments": jnp.zeros((1, len(t)), jnp.int32)},
                         pipe.cfg, pipe.plan, compute_dtype=jnp.float32)
        want = np.asarray(T.apply_head(h, pipe.params, "cls"))[0]
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-6)


def test_runtime_matches_pipeline_forward(bert_pipe):
    """Full-bucket (no padding) runtime output is bit-identical to the
    staged Pipeline forward it replaced."""
    pipe = bert_pipe
    b = pipe._model_inputs(get_batch(pipe.task, 0, 8, "dev"))
    got = pipe.predict_logits(b)
    want = np.asarray(pipe.forward(pipe.params, b))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# batch invariance (satellite): alone == inside a full batch
# ---------------------------------------------------------------------------


def test_encoder_micro_batch_invariance(bert_pipe):
    """The same request served alone and inside a full micro-batch must
    produce identical logits."""
    pipe = bert_pipe
    rng = np.random.default_rng(7)
    probe = rng.integers(1, pipe.cfg.vocab_size, size=9).tolist()

    def serve(requests):
        eng = EncoderServeEngine(pipe.cfg, pipe.params, pipe.plan,
                                 target=pipe.target.spec,
                                 compute_dtype=jnp.float32, max_batch=8)
        for i, toks in enumerate(requests):
            eng.submit(EncoderRequest(uid=i, tokens=toks))
        return {r.uid: r for r in eng.run()}

    alone = serve([probe])[0]
    fillers = [rng.integers(1, pipe.cfg.vocab_size,
                            size=int(rng.integers(3, 14))).tolist()
               for _ in range(7)]
    full = serve([probe] + fillers)[0]
    np.testing.assert_array_equal(alone.logits, full.logits)
    assert int(alone.prediction) == int(full.prediction)


def test_decode_slot_batch_invariance(qwen_setup):
    """The same request decoded alone and alongside a full slot batch must
    produce identical tokens."""
    cfg, params, plan = qwen_setup
    probe = [5, 9, 3, 7]

    def generate(extra):
        eng = ServeEngine(cfg, params, plan, batch_slots=4, max_len=64)
        eng.submit(Request(uid=0, prompt=probe, max_tokens=6))
        for i, p in enumerate(extra, start=1):
            eng.submit(Request(uid=i, prompt=p, max_tokens=6))
        return {r.uid: r.output for r in eng.run()}

    alone = generate([])[0]
    full = generate([[11, 2], [4, 4, 8, 1, 9], [13]])[0]
    assert alone == full


# ---------------------------------------------------------------------------
# the executable cache
# ---------------------------------------------------------------------------


def test_retrace_at_most_once_per_bucket(bert_pipe):
    """A mixed-length request stream compiles at most once per
    (batch, length) bucket — the retrace counter proves it."""
    pipe = bert_pipe
    eng = EncoderServeEngine(pipe.cfg, pipe.params, pipe.plan,
                             target=pipe.target.spec,
                             compute_dtype=jnp.float32, max_batch=4)
    rng = np.random.default_rng(0)
    uid = 0
    for _ in range(2):                      # the second pass must be free
        for n in (3, 7, 9, 12, 16, 5, 10):  # buckets: 8 and 16
            eng.submit(EncoderRequest(
                uid=uid,
                tokens=rng.integers(1, pipe.cfg.vocab_size, size=n)
                .tolist()))
            uid += 1
            eng.run()
    s = eng.stats
    assert s["retired"] == uid
    # buckets seen: (batch=1, len=8/16) (+ possibly (2/4, ...) — but each
    # distinct bucket traced exactly once
    assert s["runtime_traces"] == s["runtime_executables"]
    before = eng.stats["runtime_traces"]
    eng.submit(EncoderRequest(uid=uid, tokens=[1, 2, 3]))
    eng.run()
    assert eng.stats["runtime_traces"] == before    # bucket already cached


def test_pipeline_predict_reuses_buckets(bert_pipe):
    pipe = bert_pipe
    rt = pipe.runtime
    before = rt.stats["traces"]
    for bs in (8, 8, 8):
        pipe.predict(get_batch(pipe.task, bs, bs, "dev"))
    assert rt.stats["traces"] <= before + 1


def test_shared_runtime_keeps_trace_count_honest(qwen_setup):
    """Two engines sharing one Runtime with different cache geometries must
    get distinct cache entries — traces stays == executables."""
    cfg, params, plan = qwen_setup
    rt = Runtime(cfg, plan, compute_dtype=jnp.float32)
    for max_len in (32, 64):
        eng = ServeEngine(cfg, params, plan, batch_slots=2,
                          max_len=max_len, runtime=rt)
        eng.submit(Request(uid=0, prompt=[3, 5], max_tokens=2))
        eng.run()
    s = rt.stats
    assert s["traces"] == s["executables"] == 2


def test_decode_engine_single_executable(qwen_setup):
    cfg, params, plan = qwen_setup
    eng = ServeEngine(cfg, params, plan, batch_slots=3, max_len=64)
    for i in range(5):
        eng.submit(Request(uid=i, prompt=[3 + i, 5], max_tokens=3))
    eng.run()
    assert eng.stats["runtime_traces"] == 1
    assert eng.stats["runtime_executables"] == 1


# ---------------------------------------------------------------------------
# SAMP.serve() dispatch + encoder end-to-end
# ---------------------------------------------------------------------------


def test_samp_serve_dispatches_encoder_engine(bert_pipe):
    samp = SAMP(bert_pipe)
    server = samp.serve(batch_slots=4, max_len=64)
    assert isinstance(server, EncoderServeEngine)
    # ... and shares the pipeline's runtime (one executable cache)
    assert server.runtime is bert_pipe.runtime


def test_samp_serve_dispatches_decode_engine():
    cfg = get_config("qwen2-0.5b").reduced()
    samp = SAMP.from_config(cfg, task="lm", seq_len=16,
                            float_dtype="float32")
    samp.pipeline.init_params(KEY)
    assert isinstance(samp.serve(max_len=32), ServeEngine)


def test_encoder_config_serves_quantized_end_to_end():
    """Acceptance: an encoder-only config autotuned through the facade
    serves classification requests via SAMP.serve(), and engine
    predictions match pipeline predictions."""
    cfg = tiny_bert()
    samp = SAMP.from_config(cfg, task="tnews", seq_len=16,
                            float_dtype="float32")
    samp.pipeline.init_params(KEY)
    samp.calibrate(num_batches=2, batch_size=4)
    samp.apply(make_policy(cfg, "ffn", "float32"))
    server = samp.serve(batch_slots=8, max_len=16)
    assert isinstance(server, EncoderServeEngine)
    b = get_batch(samp.task, 0, 6, "dev")
    for i in range(6):
        server.submit(EncoderRequest(
            uid=i, tokens=[int(t) for t in b["tokens"][i]],
            segments=[int(s) for s in b["segments"][i]]))
    done = {r.uid: r for r in server.run()}
    assert len(done) == 6
    want = samp.predict(b)
    got = np.asarray([int(done[i].prediction) for i in range(6)])
    np.testing.assert_array_equal(got, want)


def test_seq_labeling_requests_get_per_token_predictions(bert_pipe):
    cfg = tiny_bert()
    pipe = Pipeline.build(cfg, "ner", seq_len=16, float_dtype="float32")
    pipe.init_params(KEY)
    eng = EncoderServeEngine(cfg, pipe.params, pipe.plan,
                             target=pipe.target.spec,
                             compute_dtype=jnp.float32)
    eng.submit(EncoderRequest(uid=0, tokens=[4, 9, 2, 7, 1]))
    req = eng.run()[0]
    assert req.logits.shape == (5, pipe.target.n_out)
    assert req.prediction.shape == (5,)


def test_encoder_engine_validation(bert_pipe):
    pipe = bert_pipe
    eng = EncoderServeEngine(pipe.cfg, pipe.params, pipe.plan,
                             target=pipe.target.spec, max_len=16)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(EncoderRequest(uid=0, tokens=[]))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(EncoderRequest(uid=0, tokens=[1] * 17))
    with pytest.raises(ValueError, match="segments"):
        eng.submit(EncoderRequest(uid=0, tokens=[1, 2], segments=[0]))
    with pytest.raises(ValueError, match="head"):
        params = {k: v for k, v in pipe.params.items() if k != "head"}
        EncoderServeEngine(pipe.cfg, params, pipe.plan,
                           target=pipe.target.spec)
