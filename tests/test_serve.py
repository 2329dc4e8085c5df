"""Serving engine: continuous batching correctness + SAMP integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import LayerMode, PrecisionPlan
from repro.core.samp import SAMPEngine
from repro.models import transformer as T
from repro.serve import Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def greedy_reference(cfg, params, plan, prompt, n, max_len=64):
    caches = T.init_caches(cfg, plan, 1, max_len, jnp.float32)
    out = []
    for t in range(len(prompt) + n - 1):
        tok = prompt[t] if t < len(prompt) else out[-1]
        lg, caches = T.decode_step(params, jnp.asarray([[tok]], jnp.int32),
                                   caches, t, cfg, plan,
                                   compute_dtype=jnp.float32)
        if t >= len(prompt) - 1:
            out.append(int(jnp.argmax(lg[0, 0])))
    return out[:n]


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = get_config("qwen2-0.5b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    return cfg, params, plan


def test_continuous_batching_matches_sequential(qwen_setup):
    cfg, params, plan = qwen_setup
    eng = ServeEngine(cfg, params, plan, batch_slots=3, max_len=64)
    prompts = [[5, 9, 3], [7, 2], [11, 4, 6, 8], [1, 2, 3], [9]]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_tokens=5))
    done = eng.run()
    assert len(done) == len(prompts)
    for req in done:
        want = greedy_reference(cfg, params, plan, req.prompt, 5)
        assert req.output == want, req.uid


def test_slot_reuse_is_clean(qwen_setup):
    """Later requests in a reused slot see a fresh cache."""
    cfg, params, plan = qwen_setup
    eng = ServeEngine(cfg, params, plan, batch_slots=1, max_len=64)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[3 + i, 5], max_tokens=4))
    done = sorted(eng.run(), key=lambda r: r.uid)
    for req in done:
        want = greedy_reference(cfg, params, plan, req.prompt, 4)
        assert req.output == want


def test_eos_stops_early(qwen_setup):
    cfg, params, plan = qwen_setup
    # find what greedy produces, then use its first token as EOS
    first = greedy_reference(cfg, params, plan, [5, 9, 3], 1)[0]
    eng = ServeEngine(cfg, params, plan, batch_slots=2, max_len=64)
    eng.submit(Request(uid=0, prompt=[5, 9, 3], max_tokens=10,
                       eos_id=first))
    done = eng.run()
    assert done[0].output == [first]


def test_temperature_sampling_runs(qwen_setup):
    cfg, params, plan = qwen_setup
    eng = ServeEngine(cfg, params, plan, batch_slots=2, max_len=64, seed=1)
    eng.submit(Request(uid=0, prompt=[5, 9], max_tokens=6, temperature=1.0))
    done = eng.run()
    assert len(done[0].output) == 6


def test_validation_errors(qwen_setup):
    cfg, params, plan = qwen_setup
    eng = ServeEngine(cfg, params, plan, batch_slots=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=[], max_tokens=4))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=[1] * 15, max_tokens=4))
    cfg_enc = get_config("hubert-xlarge").reduced()
    with pytest.raises(ValueError):
        ServeEngine(cfg_enc, params, plan)


def test_serving_quantized_model():
    """SAMP-quantized weights serve through the same engine."""
    cfg = get_config("qwen2-0.5b").reduced()
    eng_s = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng_s.float_precision)
    batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (2, 16),
                                             0, cfg.vocab_size)}
               for i in range(2)]
    stats = eng_s.calibrate(params, batches)
    policy = PrecisionPlan.prefix(cfg.num_layers, cfg.num_layers,
                                  LayerMode.QUANT_FFN_ONLY, "float32")
    qp, plan = eng_s.apply(params, stats, policy)
    srv = ServeEngine(cfg, qp, plan, batch_slots=2, max_len=32)
    srv.submit(Request(uid=0, prompt=[5, 9, 3], max_tokens=4))
    done = srv.run()
    assert len(done[0].output) == 4


def test_recurrent_arch_serving():
    """Continuous batching over SSM state (xlstm) — state gating path."""
    cfg = get_config("xlstm-125m").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    plan = T.build_plan(cfg, policy)
    params = T.init_params(KEY, cfg, policy)
    eng = ServeEngine(cfg, params, plan, batch_slots=2, max_len=32)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=[4 + i, 7, 2], max_tokens=4))
    done = sorted(eng.run(), key=lambda r: r.uid)
    for req in done:
        want = greedy_reference(cfg, params, plan, req.prompt, 4, max_len=32)
        assert req.output == want
