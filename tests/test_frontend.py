"""HTTP/SSE serving front-end: protocol units, scheduler cancellation,
and the acceptance contracts — concurrent HTTP clients get the SAME
numbers as the direct engine/pipeline path on the golden plan, and an
over-capacity burst is answered with 429s that show up at /metrics."""
import asyncio
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

from serve_http_load import http_json, http_sse, scrape_metrics

from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.launch.serve import build_model
from repro.serve import (EncoderRequest, MicroBatcher, Request, ServeEngine,
                         SlotScheduler)
from repro.serve.frontend import HTTPFrontend
from repro.serve.frontend import protocol as P
from repro.serve.metrics import (CORE_METRICS, engine_counters,
                                 latency_summary)
from repro.toolkit import SAMP

KEY = jax.random.PRNGKey(0)
GOLDEN = os.path.join(os.path.dirname(__file__), "data", "golden_plan.json")
SILENT = lambda *a, **k: None  # noqa: E731


def run_session(fe: HTTPFrontend, scenario):
    """Boot ``fe``, run ``scenario(port)`` against it, always stop."""

    async def main():
        await fe.start()
        try:
            return await scenario(fe.port)
        finally:
            await fe.stop()

    return asyncio.run(main())


# ---------------------------------------------------------------------------
# protocol + metrics units (no model)
# ---------------------------------------------------------------------------


def test_sse_event_roundtrip():
    frames = (P.sse_event("token", {"token": 7, "index": 0})
              + P.sse_event("done", {"tokens": [7], "finish_reason": "stop"}))
    got = P.parse_sse(frames.decode("utf-8"))
    assert got == [("token", {"token": 7, "index": 0}),
                   ("done", {"tokens": [7], "finish_reason": "stop"})]


def test_read_request_parses_body_and_rejects_garbage():
    async def check():
        body = b'{"tokens": [1, 2]}'
        r = asyncio.StreamReader()
        r.feed_data(b"POST /v1/encode HTTP/1.1\r\nHost: x\r\n"
                    b"Content-Length: %d\r\n\r\n%b" % (len(body), body))
        r.feed_eof()
        req = await P.read_request(r)
        assert (req.method, req.path) == ("POST", "/v1/encode")
        assert req.json() == {"tokens": [1, 2]}

        bad = asyncio.StreamReader()
        bad.feed_data(b"NOT A REQUEST\r\n\r\n")
        bad.feed_eof()
        with pytest.raises(P.ProtocolError):
            await P.read_request(bad)

    asyncio.run(check())


def test_response_always_closes_connection():
    raw = P.json_response(429, {"error": "x"},
                          headers={"Retry-After": "1"}).decode("latin1")
    head = raw.split("\r\n\r\n")[0]
    assert "HTTP/1.1 429" in head
    assert "Connection: close" in head
    assert "Retry-After: 1" in head


def test_latency_summary_buckets_are_cumulative():
    s = latency_summary([0.002, 0.004, 0.2, 3.0, 0.3], buckets=(0.005, 0.5))
    assert s["count"] == 5
    assert s["latency_buckets"] == {"0.005": 2, "0.5": 4, "+Inf": 5}
    assert s["p50_latency_s"] == 0.2            # nearest-rank median
    assert s["p99_latency_s"] == 3.0


# ---------------------------------------------------------------------------
# scheduler-level cancellation units (no model)
# ---------------------------------------------------------------------------


def test_slot_scheduler_cancel_queued_and_active():
    sched = SlotScheduler(slots=1)
    a = Request(uid=0, prompt=[1, 2], max_tokens=4)
    b = Request(uid=1, prompt=[3], max_tokens=4)
    sched.submit(a)
    sched.submit(b)
    assert sched.admit() == [0] and sched.active[0] is a
    assert sched.cancel(b) == "queued"          # evicted before a slot
    assert sched.cancel(a) == "active"          # slot released mid-flight
    assert sched.live() == [] and sched.evicted == 2
    assert sched.cancel(a) is None              # already gone


def test_microbatcher_evict_preserves_queue_order():
    mb = MicroBatcher(max_batch=8, max_wait=100.0, min_len=8)
    reqs = [EncoderRequest(uid=i, tokens=[1] * 5) for i in range(4)]
    for r in reqs:
        mb.submit(r, now=0.0)
    gone = mb.evict(lambda r: r.uid in (1, 3))
    assert [r.uid for r in gone] == [1, 3] and mb.evicted == 2
    assert len(mb) == 2
    assert mb.cancel(reqs[0]) and not mb.cancel(reqs[0])
    got = mb.ready(now=0.0, force=True)
    assert [q.uid for _, qs in got for q in qs] == [2]  # order kept


# ---------------------------------------------------------------------------
# encoder acceptance: HTTP == Pipeline.predict on the golden plan
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def bert_golden():
    """Golden-plan-quantized bert facade; engines built from it share the
    quantized pipeline's runtime (one executable cache per module)."""
    samp = SAMP.from_config(get_config("bert-base").reduced(), task="tnews",
                            seq_len=32, float_dtype="float32")
    samp.pipeline.init_params(KEY)
    samp.calibrate(num_batches=1, batch_size=4,
                   precision=PrecisionPlan.load(GOLDEN))
    qpipe = samp.apply_plan_file(GOLDEN)
    return samp, qpipe


def test_concurrent_encode_matches_pipeline_and_metrics(bert_golden):
    """Two concurrent HTTP clients must read the SAME logits the direct
    Pipeline.predict path computes (no transport-induced numeric drift),
    and a /metrics scrape must expose the full core catalog."""
    samp, qpipe = bert_golden
    fe = samp.serve_http(port=0, batch_slots=4, max_len=32, max_wait=0.01,
                         log=SILENT)
    toks = [[5, 9, 3, 7, 2, 11], [4, 8, 1, 6, 2, 9, 10, 3]]
    # the engine always feeds segment ids on segment-aware archs (zeros
    # when the request states none), so the direct batch must too
    batches = [{"tokens": np.asarray([t]),
                "segments": np.zeros((1, len(t)), np.int32)} for t in toks]
    direct = [qpipe.predict_logits(b)[0] for b in batches]
    direct_pred = [int(qpipe.predict(b)[0]) for b in batches]

    async def scenario(port):
        results = await asyncio.gather(
            *(http_json("127.0.0.1", port, "POST", "/v1/encode",
                        {"tokens": t}) for t in toks))
        metrics = await scrape_metrics("127.0.0.1", port)
        return results, metrics

    results, metrics = run_session(fe, scenario)
    for (status, _, obj), want, want_pred in zip(results, direct,
                                                 direct_pred):
        assert status == 200
        np.testing.assert_allclose(np.asarray(obj["logits"]),
                                   np.asarray(want), rtol=0, atol=1e-5)
        assert obj["prediction"] == want_pred
    for name in CORE_METRICS:
        assert name in metrics, name
    assert 'samp_build_info{backend="reference",engine="encoder"' in metrics
    assert 'samp_requests_admitted_total 2' in metrics


def test_burst_over_capacity_yields_429_and_rejection_counter(bert_golden):
    """6 concurrent clients against max_pending=2 with a long micro-batch
    ageing window: exactly 4 must get 429 + Retry-After, and the rejection
    counter must be visible at /metrics before the server stops."""
    samp, _ = bert_golden
    engine = samp.serve(batch_slots=8, max_len=32, max_wait=0.5)
    fe = HTTPFrontend(encoder=engine, port=0, max_pending=2, log=SILENT)

    async def scenario(port):
        results = await asyncio.gather(
            *(http_json("127.0.0.1", port, "POST", "/v1/encode",
                        {"tokens": [3 + i, 5, 9, 2]})
              for i in range(6)))
        metrics = await scrape_metrics("127.0.0.1", port)
        return results, metrics

    results, metrics = run_session(fe, scenario)
    by_status = sorted(status for status, _, _ in results)
    assert by_status == [200, 200, 429, 429, 429, 429]
    for status, headers, obj in results:
        if status == 429:
            assert headers.get("retry-after") == "1"
            assert obj["reason"] == "capacity"
    assert ('samp_requests_rejected_total{reason="capacity"} 4'
            in metrics), metrics
    assert fe.driver.counts["rejected_capacity"] == 4


def test_deadline_expiry_evicts_queued_microbatch_request(bert_golden):
    """A queued encoder request whose deadline passes before its bucket
    ages out must be evicted from the MicroBatcher (never batched) and
    answered 504."""
    samp, _ = bert_golden
    engine = samp.serve(batch_slots=8, max_len=32, max_wait=10.0)
    evicted_before = engine.batcher.evicted
    fe = HTTPFrontend(encoder=engine, port=0, log=SILENT)

    async def scenario(port):
        t0 = time.monotonic()
        status, _, obj = await http_json(
            "127.0.0.1", port, "POST", "/v1/encode",
            {"tokens": [5, 9, 3], "deadline_ms": 100})
        return status, obj, time.monotonic() - t0

    status, obj, took = run_session(fe, scenario)
    assert status == 504 and "deadline" in obj["error"]
    assert took < 5.0                           # never waited out max_wait
    assert engine.batcher.evicted == evicted_before + 1
    assert fe.driver.counts["cancelled_deadline"] == 1
    assert engine._stats["batches"] == 0     # never batched, only evicted
    assert len(engine.batcher) == 0


def test_drain_completes_inflight_and_rejects_new(bert_golden):
    """SIGTERM semantics (begin_drain): the queued in-flight request is
    force-flushed to a 200, a post-drain submission gets 503, and the
    server task returns."""
    samp, _ = bert_golden
    engine = samp.serve(batch_slots=8, max_len=32, max_wait=30.0)
    fe = HTTPFrontend(encoder=engine, port=0, log=SILENT)

    async def scenario(port):
        inflight = asyncio.create_task(http_json(
            "127.0.0.1", port, "POST", "/v1/encode",
            {"tokens": [7, 2, 9, 4]}))
        for _ in range(100):                    # wait until it is admitted
            if fe.driver.inflight:
                break
            await asyncio.sleep(0.01)
        assert fe.driver.inflight == 1
        fe.begin_drain()
        rejected = await http_json("127.0.0.1", port, "POST", "/v1/encode",
                                   {"tokens": [1, 2, 3]})
        completed = await inflight
        await asyncio.wait_for(fe.serve_forever(), timeout=30)
        return completed, rejected

    (st_ok, _, obj_ok), (st_no, hdr_no, _) = run_session(fe, scenario)
    assert st_ok == 200 and "logits" in obj_ok  # drained, not dropped
    assert st_no == 503 and hdr_no.get("retry-after") == "5"
    assert fe.driver.counts["rejected_draining"] == 1


def test_engine_stats_and_metrics_share_one_surface(bert_golden):
    """Satellite 2: engine.stats must carry exactly the engine_counters
    numbers /metrics samples — one source of truth."""
    samp, _ = bert_golden
    engine = samp.serve(batch_slots=4, max_len=32)
    counters = engine_counters(engine)
    stats = engine.stats
    for key in ("queue_depth", "occupancy", "capacity", "completed",
                "evicted", "retraces", "executables"):
        assert stats[key] == counters[key], key


# ---------------------------------------------------------------------------
# decode acceptance: SSE stream == direct ServeEngine.run on the golden plan
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen_golden():
    cfg = get_config("qwen2-0.5b").reduced()
    params, plan, _ = build_model(cfg, plan_file=GOLDEN, log=SILENT)
    return cfg, params, plan


def test_concurrent_sse_decode_matches_direct_engine(qwen_golden):
    cfg, params, plan = qwen_golden
    prompts = [[2, 17, 9], [5, 40]]
    direct = ServeEngine(cfg, params, plan, batch_slots=2, max_len=48)
    for i, p in enumerate(prompts):
        direct.submit(Request(uid=i, prompt=list(p), max_tokens=5))
    want = {tuple(r.prompt): r.output for r in direct.run()}

    fe = HTTPFrontend(decode=ServeEngine(cfg, params, plan, batch_slots=2,
                                         max_len=48),
                      port=0, log=SILENT)

    async def scenario(port):
        streams = await asyncio.gather(
            *(http_sse("127.0.0.1", port, "/v1/generate",
                       {"prompt": p, "max_tokens": 5}) for p in prompts))
        return streams, await scrape_metrics("127.0.0.1", port)

    results, metrics = run_session(fe, scenario)
    # greedy requests take the device's picks: no tick copied logits
    assert "\nsamp_logits_fetches_total 0\n" in metrics
    for p, (status, _, events) in zip(prompts, results):
        assert status == 200
        streamed = [d["token"] for e, d in events if e == "token"]
        done = [d for e, d in events if e == "done"]
        assert len(done) == 1
        assert done[0]["tokens"] == streamed    # stream == final transcript
        assert streamed == want[tuple(p)]       # == direct engine decode
        assert [d["index"] for e, d in events if e == "token"] == \
            list(range(len(streamed)))


def test_disconnect_mid_decode_releases_slot(qwen_golden):
    """A client that vanishes mid-stream must free its slot (slots=1, so a
    follow-up request can only complete if the first was cancelled)."""
    cfg, params, plan = qwen_golden
    engine = ServeEngine(cfg, params, plan, batch_slots=1, max_len=48)
    fe = HTTPFrontend(decode=engine, port=0, log=SILENT)

    async def scenario(port):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = b'{"prompt": [2, 17, 9], "max_tokens": 40}'
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                     b"Content-Length: %d\r\n\r\n%b" % (len(body), body))
        await writer.drain()
        buf = b""
        while buf.count(b"event: token") < 2:   # mid-generation, provably
            buf += await reader.read(512)
        writer.close()                          # client vanishes
        await writer.wait_closed()
        for _ in range(300):                    # slot must come free
            if not engine.sched.live() and not fe.driver.inflight:
                break
            await asyncio.sleep(0.02)
        assert not engine.sched.live()
        status, _, events = await http_sse(     # slot is reusable
            "127.0.0.1", port, "/v1/generate",
            {"prompt": [5, 40], "max_tokens": 3})
        return status, events

    status, events = run_session(fe, scenario)
    assert status == 200
    assert len([d for e, d in events if e == "done"]) == 1
    assert engine.sched.evicted >= 1
    assert fe.driver.counts["cancelled_disconnect"] == 1
