"""Spans and counters inside the serving engines: the ``samp.<engine>.<phase>``
phase table, the schedulers' queue-wait counters, the profiler spans they
open and the ``/metrics`` series that export them."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.models import transformer as T
from repro.serve import (EncoderRequest, EncoderServeEngine, MicroBatcher,
                         Request, ServeEngine, SlotScheduler)
from repro.serve.frontend.server import HTTPFrontend
from repro.serve.metrics import PHASES, Phases
from repro.toolkit import Pipeline

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def bert_pipe():
    cfg = get_config("bert-base").reduced().replace(num_layers=2)
    pipe = Pipeline.build(cfg, "tnews", seq_len=16, float_dtype="float32")
    pipe.init_params(KEY)
    return pipe


@pytest.fixture(scope="module")
def qwen_setup():
    cfg = get_config("qwen2-0.5b").reduced()
    policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
    return cfg, T.init_params(KEY, cfg, policy), T.build_plan(cfg, policy)


def encoder(pipe, **kw):
    return EncoderServeEngine(pipe.cfg, pipe.params, pipe.plan,
                              target=pipe.target.spec,
                              compute_dtype=jnp.float32, **kw)


def decoder(setup, **kw):
    cfg, params, plan = setup
    return ServeEngine(cfg, params, plan, max_len=64, **kw)


def submit_encoder(eng, lengths):
    for uid, n in enumerate(lengths):
        eng.submit(EncoderRequest(uid=uid, tokens=[4 + i % 50
                                                   for i in range(n)]))


def test_encoder_phase_counts_follow_micro_batches(bert_pipe):
    eng = encoder(bert_pipe, max_batch=2, max_wait=1e9)
    submit_encoder(eng, [5, 6, 7, 12, 30])   # buckets 8: 3 rows, 16, 32
    assert eng.step() != []                  # the full 8-bucket batch only
    done = eng.run()
    assert len(done) == 3
    n, s = eng.stats["phase_n"], eng.stats["phase_s"]
    batches = eng.stats["batches"]
    assert batches == 4
    assert n["samp.enc.step"] == eng.stats["steps"] == 2
    assert n["samp.enc.flush"] == 2
    for phase in ("assemble", "pad", "dispatch", "fetch", "predict"):
        assert n[f"samp.enc.{phase}"] == batches, phase
    children = sum(s[f"samp.enc.{p}"] for p in PHASES["enc"][1:])
    assert children <= s["samp.enc.step"]


def test_decode_phase_counts_follow_ticks(qwen_setup):
    eng = decoder(qwen_setup, batch_slots=2)
    for uid, prompt in enumerate([[5, 9, 3], [7, 2], [11, 4]]):
        eng.submit(Request(uid=uid, prompt=prompt, max_tokens=3))
    assert len(eng.run()) == 3
    st = eng.stats
    n, s = st["phase_n"], st["phase_s"]
    assert n["samp.dec.tick"] == n["samp.dec.admit"] == st["steps"]
    for phase in ("assemble", "dispatch", "fetch", "sample"):
        assert n[f"samp.dec.{phase}"] == st["ticks"], phase
    assert "samp.dec.pages" not in n          # dense caches: no page pool
    children = sum(s.get(f"samp.dec.{p}", 0.0) for p in PHASES["dec"][1:])
    assert children <= s["samp.dec.tick"]


def test_paged_decode_times_pages_and_drains(qwen_setup):
    eng = decoder(qwen_setup, batch_slots=2, page_size=4)
    for uid in range(3):
        eng.submit(Request(uid=uid, prompt=[3 + uid, 5], max_tokens=3))
    assert len(eng.run()) == 3
    n = eng.stats["phase_n"]
    assert n["samp.dec.pages"] == eng.stats["ticks"]
    # each retirement frees pages the next tick invalidates
    assert 1 <= n["samp.dec.drain"] <= 3


def test_micro_batcher_queue_wait_from_injected_clock():
    mb = MicroBatcher(max_batch=4, max_wait=10.0)
    a, b, c = (EncoderRequest(uid=i, tokens=[1] * 5) for i in range(3))
    mb.submit(a, now=0.0)
    mb.submit(b, now=1.5)
    assert mb.ready(now=2.0) == []           # neither full nor overdue
    assert (mb.queue_wait_s, mb.queue_waited) == (0.0, 0)
    mb.submit(c, now=4.0)
    (_, batch), = mb.ready(now=10.0)         # a's wait reaches max_wait
    assert batch == [a, b, c]
    assert [r.queue_wait for r in batch] == [10.0, 8.5, 6.0]
    assert mb.queue_wait_s == pytest.approx(24.5)
    assert mb.queue_waited == 3


def test_slot_scheduler_queue_wait_from_injected_clock():
    sched = SlotScheduler(1)
    a = Request(uid=0, prompt=[1], max_tokens=2)
    b = Request(uid=1, prompt=[2], max_tokens=2)
    sched.submit(a, now=0.0)
    sched.submit(b, now=1.0)
    assert sched.admit(now=2.0) == [0]
    assert (a.queue_wait, sched.queue_wait_s, sched.queue_waited) == \
        (2.0, 2.0, 1)
    # preempted: a goes back to the queue's head, queued anew
    assert sched.preempt(0, now=3.0) is a
    assert list(sched.queue) == [a, b]
    assert sched.admit(now=7.0) == [0]
    assert sched.active[0] is a and a.queue_wait == pytest.approx(6.0)
    sched.release(0)
    assert sched.admit(now=9.5) == [0]
    assert b.queue_wait == pytest.approx(8.5)
    assert sched.queue_wait_s == pytest.approx(14.5)
    assert sched.queue_waited == 3


def test_requests_record_the_step_that_served_them(bert_pipe, qwen_setup):
    enc = encoder(bert_pipe, max_batch=8, max_wait=0.0)
    submit_encoder(enc, [5, 6])
    enc.step()
    submit_encoder(enc, [7])
    (late,) = enc.step()
    assert late.step == enc.stats["steps"] == 2
    dec = decoder(qwen_setup, batch_slots=1)
    reqs = [Request(uid=i, prompt=[3, 4], max_tokens=1) for i in range(2)]
    for r in reqs:
        dec.submit(r)
    dec.run()
    # one slot: the second request is admitted on the tick after the
    # first retires
    assert reqs[0].step == 1 and reqs[1].step == 3


def _host_spans(trace_dir) -> list:
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("samp."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return spans


def _inside(spans, inner: str, outer: str) -> None:
    outs = [s for s in spans if s[0] == outer]
    ins = [s for s in spans if s[0] == inner]
    assert ins and outs
    for _, a, b, _ in ins:
        assert any(oa <= a and b <= ob for _, oa, ob, _ in outs), (inner, a)


def test_fetch_spans_lie_inside_their_steps(bert_pipe, qwen_setup, tmp_path):
    enc = encoder(bert_pipe, max_batch=2, max_wait=0.0)
    dec = decoder(qwen_setup, batch_slots=2)
    submit_encoder(enc, [5, 9])
    enc.step()                         # compile outside the trace
    dec.submit(Request(uid=0, prompt=[5, 9], max_tokens=2))
    dec.run()
    first = dec.stats["steps"] + 1
    with jax.profiler.trace(str(tmp_path)):
        submit_encoder(enc, [6, 10])
        enc.step()
        dec.submit(Request(uid=1, prompt=[3, 8], max_tokens=2))
        dec.run()
    spans = _host_spans(tmp_path)
    _inside(spans, "samp.enc.fetch", "samp.enc.step")
    _inside(spans, "samp.dec.fetch", "samp.dec.tick")
    _inside(spans, "samp.dec.dispatch", "samp.dec.tick")
    steps = [st for name, _, _, st in spans if name == "samp.dec.tick"]
    # prompt, prompt, then the second token: three ticks, numbered on
    assert [st["step"] for st in steps] == [first, first + 1, first + 2]


def test_stats_snapshots_are_values(qwen_setup):
    eng = decoder(qwen_setup, batch_slots=2)
    eng.submit(Request(uid=0, prompt=[5, 9, 3], max_tokens=4))
    before = dict(eng.stats)
    kept = {k: dict(v) for k, v in before.items() if isinstance(v, dict)}
    eng.step()
    after = eng.stats
    assert after["phase_n"] != before["phase_n"]
    assert after["phase_s"] != before["phase_s"]
    assert after["queue_waited"] == before["queue_waited"] + 1
    # the first snapshot did not move with the engine
    assert {k: before[k] for k in kept} == kept


def test_phases_table_adds_seconds_and_calls():
    ph = Phases()
    for _ in range(3):
        with ph("samp.dec.tick", step=1):
            with ph("samp.dec.fetch"):
                pass
    snap = ph.snapshot()
    assert snap["phase_n"] == {"samp.dec.tick": 3, "samp.dec.fetch": 3}
    secs = snap["phase_s"]
    assert secs["samp.dec.fetch"] <= secs["samp.dec.tick"]
    snap["phase_n"]["samp.dec.tick"] = 0        # a copy, not the table
    assert ph.calls["samp.dec.tick"] == 3


def test_metrics_scrape_exports_phases_and_queue_wait(bert_pipe, qwen_setup):
    enc = encoder(bert_pipe, max_batch=2, max_wait=0.0)
    dec = decoder(qwen_setup, batch_slots=2)
    fe = HTTPFrontend(encoder=enc, decode=dec, port=0, log=lambda *a: None)
    submit_encoder(enc, [5, 6])
    enc.step()
    text = fe.registry.render()
    for name in ("samp_phase_seconds_total", "samp_phase_calls_total",
                 "samp_queue_wait_seconds_total", "samp_queue_waited_total"):
        assert f"# TYPE {name} counter" in text, name
    for engine, short in (("decode", "dec"), ("encoder", "enc")):
        for phase in PHASES[short]:
            assert (f'samp_phase_calls_total{{engine="{engine}",'
                    f'phase="{phase}"}}') in text
    assert ('samp_phase_calls_total{engine="encoder",phase="dispatch"} 1'
            in text)
    assert 'samp_queue_waited_total{engine="encoder"} 2' in text
    assert 'samp_queue_waited_total{engine="decode"} 0' in text
