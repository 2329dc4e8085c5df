"""repro.adaptive: input-adaptive precision end to end — cluster models,
PlanSets, cluster-conditional calibration, plan routing, and the serving
acceptance demo (routed responses bit-match single-plan serving, K
executables per bucket, the two routing metrics at /metrics)."""
import asyncio
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))

from serve_http_load import http_json, scrape_metrics

from repro.adaptive import (EmbeddingKMeans, LengthBuckets, PlanSet,
                            TaskLabel, batch_clusters, build_router,
                            cluster_model_from_dict,
                            clustered_synthetic_batches, fit_cluster_model,
                            load_plan_or_planset, pooled_embeddings)
from repro.configs import get_config
from repro.core.plan import PrecisionPlan
from repro.core.precision import make_policy
from repro.core.samp import SAMPEngine
from repro.models import transformer as T
from repro.serve import (EncoderRequest, EncoderServeEngine, MicroBatcher,
                         Request, ServeEngine, SlotScheduler)
from repro.toolkit import SAMP, load_artifact
from repro.toolkit.plan_lint import main as plan_lint_main

KEY = jax.random.PRNGKey(0)
SILENT = lambda *a, **k: None  # noqa: E731


def tiny_cfg(num_layers=2):
    return get_config("bert-base").reduced().replace(num_layers=num_layers)


def _ffn_plan(cfg):
    return make_policy(cfg, "ffn")


def _mha_plan(cfg):
    return make_policy(cfg, "full")


# ---------------------------------------------------------------------------
# PlanSet schema
# ---------------------------------------------------------------------------


def test_planset_roundtrip_fingerprint_and_lookup():
    cfg = tiny_cfg()
    ps = PlanSet(((0, _ffn_plan(cfg)), (1, _mha_plan(cfg))), default=0)
    again = PlanSet.from_json(ps.to_json())
    assert again.fingerprint() == ps.fingerprint()
    assert again.cluster_ids == (0, 1)
    # unknown cluster ids fall back to the default member
    assert ps.plan_for(99).fingerprint() == ps.plan_for(0).fingerprint()
    assert ps.plan_for(1).fingerprint() == _mha_plan(cfg).fingerprint()
    assert ps.num_layers == cfg.num_layers
    # uniform() shares one plan content across ids; K stays the id count
    uni = PlanSet.uniform(_ffn_plan(cfg), range(3))
    assert len(uni) == 3 and uni.default == 0
    assert len({p.fingerprint() for _, p in uni.members}) == 1


def test_planset_validation_errors():
    cfg = tiny_cfg()
    p = _ffn_plan(cfg)
    with pytest.raises(ValueError, match="at least one"):
        PlanSet((), default=0)
    with pytest.raises(ValueError, match="duplicate"):
        PlanSet(((0, p), (0, p)), default=0)
    with pytest.raises(ValueError, match="default"):
        PlanSet(((0, p), (1, p)), default=7)
    with pytest.raises(ValueError):
        PlanSet(((0, p), (1, make_policy(tiny_cfg(num_layers=3), "ffn"))),
                default=0)
    # strict from_dict: unknown top-level and member keys rejected
    d = PlanSet(((0, p),), default=0).to_dict()
    d["extra"] = 1
    with pytest.raises(ValueError):
        PlanSet.from_dict(d)
    d = PlanSet(((0, p),), default=0).to_dict()
    d["members"][0]["extra"] = 1
    with pytest.raises(ValueError):
        PlanSet.from_dict(d)


def test_load_plan_or_planset_sniffs_kind(tmp_path):
    cfg = tiny_cfg()
    single = tmp_path / "plan.json"
    single.write_text(_ffn_plan(cfg).to_json())
    setf = tmp_path / "planset.json"
    setf.write_text(PlanSet.single(_ffn_plan(cfg)).to_json())
    assert isinstance(load_plan_or_planset(str(single)), PrecisionPlan)
    assert isinstance(load_plan_or_planset(str(setf)), PlanSet)


def test_plan_lint_accepts_planset_and_rejects_bad(tmp_path, capsys):
    cfg = tiny_cfg()
    good = tmp_path / "planset.json"
    good.write_text(PlanSet(((0, _ffn_plan(cfg)), (1, _mha_plan(cfg))),
                            default=0).to_json())
    assert plan_lint_main([str(good), "--layers",
                           str(cfg.num_layers)]) == 0
    # wrong layer count -> non-zero exit
    assert plan_lint_main([str(good), "--layers", "13"]) == 1
    # corrupt member schema (unknown block in a layer) -> non-zero exit
    raw = json.loads(good.read_text())
    raw["members"][0]["plan"]["layers"][0]["nonexistent_block"] = {}
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(raw))
    assert plan_lint_main([str(bad)]) == 1
    # single-plan files keep linting exactly as before
    single = tmp_path / "plan.json"
    single.write_text(_ffn_plan(cfg).to_json())
    assert plan_lint_main([str(single), "--layers",
                           str(cfg.num_layers)]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# cluster models
# ---------------------------------------------------------------------------


def test_length_buckets_assignment():
    m = LengthBuckets((8, 16))
    assert m.num_clusters == 3
    assert m.assign([0] * 5) == 0
    assert m.assign([0] * 8) == 0
    assert m.assign([0] * 9) == 1
    assert m.assign([0] * 40) == 2
    rows = m.assign_rows({"tokens": np.zeros((3, 12), np.int32),
                          "lengths": np.asarray([4, 12, 30])})
    assert rows.tolist() == [0, 1, 2]
    # K=1 trivial model (the routed form of an unrouted deployment)
    assert LengthBuckets().num_clusters == 1
    with pytest.raises(ValueError):
        LengthBuckets((16, 8))


def test_task_label_assignment():
    m = TaskLabel(("chat", "search"))
    assert m.num_clusters == 2
    assert m.assign([1, 2], traffic_class="search") == 1
    assert m.assign([1, 2], traffic_class="nope") == 0   # default
    assert m.assign([1, 2]) == 0
    assert m.label_for(1) == "search"
    with pytest.raises(ValueError):
        TaskLabel(("a", "a"))


def test_kmeans_fit_and_jit_determinism():
    rng = np.random.default_rng(0)
    x = np.concatenate([rng.normal(0, 0.1, (20, 4)),
                        rng.normal(5, 0.1, (20, 4))]).astype(np.float32)
    m1 = EmbeddingKMeans(2, seed=3).fit(x)
    m2 = EmbeddingKMeans(2, seed=3).fit(x)
    np.testing.assert_array_equal(m1.centroids, m2.centroids)
    # assignment is pure JAX: jitted == eager, and jit is deterministic
    xs = rng.normal(2.5, 3.0, (16, 4)).astype(np.float32)
    eager = np.asarray(m1.assign_embedded(xs))
    jitted = jax.jit(m1.assign_embedded)
    np.testing.assert_array_equal(np.asarray(jitted(xs)), eager)
    np.testing.assert_array_equal(np.asarray(jitted(xs)),
                                  np.asarray(jitted(xs)))
    # serialization round-trips the fitted centroids exactly
    again = cluster_model_from_dict(m1.to_dict())
    assert again.fingerprint() == m1.fingerprint()
    np.testing.assert_array_equal(
        np.asarray(again.assign_embedded(xs)), eager)


def test_cluster_model_serialization_roundtrip():
    for m in (LengthBuckets((8, 16)), TaskLabel(("a", "b"), default=1),
              EmbeddingKMeans(3, seed=7)):
        again = cluster_model_from_dict(m.to_dict())
        assert type(again) is type(m)
        assert again.fingerprint() == m.fingerprint()
    with pytest.raises(ValueError, match="unknown cluster model"):
        cluster_model_from_dict({"kind": "astrology"})


def test_clustered_synthetic_batches_cover_every_cluster():
    cfg = tiny_cfg()
    model = LengthBuckets((8, 16))
    batches, classes = clustered_synthetic_batches(cfg, model, max_len=64)
    seen = set()
    for vec in batch_clusters(model, batches, batch_classes=classes):
        seen.update(int(c) for c in vec)
    assert seen == {0, 1, 2}
    # a max_len that cannot represent every bin is an error, not silence
    with pytest.raises(ValueError, match="cannot cover"):
        clustered_synthetic_batches(cfg, model, max_len=16)
    tl = TaskLabel(("a", "b"))
    batches, classes = clustered_synthetic_batches(cfg, tl, max_len=32)
    seen = set()
    for vec in batch_clusters(tl, batches, batch_classes=classes):
        seen.update(int(c) for c in vec)
    assert seen == {0, 1}


# ---------------------------------------------------------------------------
# cluster-conditional calibration
# ---------------------------------------------------------------------------


def test_capture_stats_clusters_partitions_rows_exactly():
    """Per-cluster stats equal single-cluster calibration on that
    cluster's rows alone — partitioning is exact, not approximate."""
    cfg = tiny_cfg()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision)

    def mk(seed, rows, width):
        return {"tokens": jax.random.randint(jax.random.PRNGKey(seed),
                                             (rows, width), 0,
                                             cfg.vocab_size),
                "segments": np.zeros((rows, width), np.int32)}

    b0, b1 = mk(0, 2, 8), mk(1, 2, 12)
    clustered = eng.calibrate(params, [b0, b1],
                              clusters=[np.zeros(2, np.int64),
                                        np.ones(2, np.int64)])
    assert set(clustered) == {0, 1}
    want0 = eng.calibrate(params, [b0])
    want1 = eng.calibrate(params, [b1])
    for want, got in ((want0, clustered[0]), (want1, clustered[1])):
        assert set(got) == set(want)
        for layer in want:
            for site, amax in want[layer].items():
                np.testing.assert_allclose(got[layer][site], amax,
                                           rtol=0, atol=0)


# ---------------------------------------------------------------------------
# cluster-pure scheduling
# ---------------------------------------------------------------------------


def test_microbatcher_flushes_all_overdue_queues_in_one_tick():
    """Regression: every overdue partial (bucket, cluster) queue must
    flush in ONE ready() call — a quiet cluster can never be stranded
    behind its siblings."""
    mb = MicroBatcher(max_batch=4, max_wait=0.01)
    reqs = []
    for uid, (n, cluster) in enumerate([(5, 0), (5, 1), (20, 0)]):
        r = EncoderRequest(uid=uid, tokens=[1] * n)
        r.cluster = cluster
        reqs.append(r)
        mb.submit(r, now=0.0)
    assert len(mb) == 3 and mb.depth_by_cluster() == {0: 2, 1: 1}
    got = mb.ready(now=1.0)          # everything overdue -> one tick
    assert len(got) == 3
    assert len(mb) == 0
    for _bucket, batch in got:
        assert len({r.cluster for r in batch}) == 1   # cluster-pure


def test_microbatcher_queues_are_cluster_pure():
    mb = MicroBatcher(max_batch=2, max_wait=10.0)
    for uid, cluster in enumerate([0, 1, 0]):
        r = EncoderRequest(uid=uid, tokens=[1] * 5)
        r.cluster = cluster
        mb.submit(r, now=0.0)
    # same length bucket, different clusters: only cluster 0 is full
    got = mb.ready(now=0.0)
    assert len(got) == 1
    assert [r.uid for r in got[0][1]] == [0, 2]
    assert mb.depth_by_cluster().get(1) == 1 and len(mb) == 1


def test_slot_scheduler_cluster_pure_admission():
    sched = SlotScheduler(2, cluster_pure=True)
    reqs = []
    for uid, cluster in enumerate([0, 1, 0]):
        r = Request(uid=uid, prompt=[1, 2], max_tokens=2)
        r.cluster = cluster
        reqs.append(r)
        sched.submit(r)
    newly = sched.admit()
    # only cluster 0 requests run together; cluster 1 keeps FIFO order
    assert [sched.active[s].uid for s in newly] == [0, 2]
    assert sched.active_cluster == 0
    assert [r.uid for r in sched.queue] == [1]
    assert sched.admit() == []       # cluster 1 waits for the batch drain
    for s in list(newly):
        sched.release(s)
    newly = sched.admit()
    assert [sched.active[s].uid for s in newly] == [1]
    assert sched.active_cluster == 1


# ---------------------------------------------------------------------------
# facade: adaptive autotune + artifact v3 round-trip
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def adaptive_samp():
    """A briefly fine-tuned 2-layer BERT facade autotuned into a K=3
    input-adaptive deployment (LengthBuckets) — shared across tests."""
    samp = SAMP.from_config(tiny_cfg(), task="tnews", seq_len=16,
                            float_dtype="float32")
    samp.finetune(steps=30, batch_size=16, log=SILENT)
    report = samp.autotune(clusters=LengthBuckets((8, 12)), stride=1,
                           eval_batches=1, eval_batch_size=16)
    samp.autotune_report = report
    return samp


def test_adaptive_autotune_builds_planset_and_router(adaptive_samp):
    samp = adaptive_samp
    assert samp.planset is not None and len(samp.planset) == 3
    assert samp.router is not None
    assert samp.router.num_clusters == 3
    assert set(samp.autotune_report.per_cluster) <= {0, 1, 2}
    assert samp.autotune_report.planset is samp.planset
    # stats are cluster-keyed and every member quantized under its own
    for cid in samp.planset.cluster_ids:
        assert cid in samp.stats


def test_cluster_stats_survive_artifact_roundtrip(adaptive_samp, tmp_path):
    """Per-(cluster, layer, site) amax round-trips through the v3 bundle
    bit-exactly, and the reloaded facade rebuilds identical quantized
    trees and predictions."""
    samp = adaptive_samp
    bundle = str(tmp_path / "bundle")
    samp.save(bundle)
    art = load_artifact(bundle)
    assert art.adaptive
    assert art.planset.fingerprint() == samp.planset.fingerprint()
    assert art.cluster_model.fingerprint() == \
        samp.cluster_model.fingerprint()
    assert set(art.cluster_stats) == set(samp.stats)
    for cid, layers in samp.stats.items():
        for layer, sites in layers.items():
            for site, amax in sites.items():
                np.testing.assert_allclose(
                    art.cluster_stats[cid][layer][site], amax,
                    rtol=0, atol=0)
    # reloaded facade: default-member predictions are bit-identical
    reloaded = SAMP.load(bundle)
    assert reloaded.router is not None
    from repro.data import get_batch
    b = get_batch(samp.task, 3, 16, "dev")
    np.testing.assert_array_equal(samp.predict(b), reloaded.predict(b))
    # every member's quantized tree rebuilds bit-identically
    for cid in samp.planset.cluster_ids:
        a = jax.tree_util.tree_leaves(samp.router.entry(cid).params)
        b_ = jax.tree_util.tree_leaves(reloaded.router.entry(cid).params)
        for x, y in zip(a, b_):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# routed serving parity (the acceptance demo, in-process)
# ---------------------------------------------------------------------------


def _req_tokens(cfg, n, seed=0):
    rng = np.random.default_rng(seed + n)
    return rng.integers(1, cfg.vocab_size, size=n).tolist()


def test_routed_serving_matches_single_plan_serving(adaptive_samp):
    """Acceptance (a): per-cluster routed responses bit-match an unrouted
    engine deployed with that cluster's (params, plan) alone, and match
    the per-cluster Pipeline logits."""
    samp = adaptive_samp
    engine = samp.serve(batch_slots=4, max_len=16, max_wait=0.0)
    assert engine.router is samp.router
    cases = {0: _req_tokens(samp.cfg, 5), 1: _req_tokens(samp.cfg, 10),
             2: _req_tokens(samp.cfg, 14)}
    done = {}
    for cid, toks in cases.items():
        req = EncoderRequest(uid=cid, tokens=toks)
        engine.submit(req)
        assert req.cluster == cid
    for r in engine.run():
        done[r.uid] = r
    assert set(done) == {0, 1, 2}
    for cid, toks in cases.items():
        entry = samp.router.entry(cid)
        # single-plan engine: same member params/plan, no router
        solo = EncoderServeEngine(samp.cfg, entry.params, entry.plan,
                                  target=samp.pipeline.target.spec,
                                  scheme=samp.pipeline.scheme,
                                  compute_dtype=samp.pipeline.compute_dtype,
                                  max_batch=4, max_len=16)
        sreq = EncoderRequest(uid=0, tokens=toks)
        solo.submit(sreq)
        solo.run()
        np.testing.assert_array_equal(done[cid].logits, sreq.logits)
        assert done[cid].prediction == sreq.prediction
        # and the pipeline view of the same member agrees numerically
        pipe_c = samp.pipeline.with_policy(entry.params, entry.plan,
                                           entry.precision)
        batch = {"tokens": np.asarray([toks]),
                 "segments": np.zeros((1, len(toks)), np.int32)}
        np.testing.assert_allclose(done[cid].logits,
                                   pipe_c.predict_logits(batch)[0],
                                   rtol=0, atol=1e-5)


def test_routed_decode_matches_single_plan_decode():
    """Decode side of acceptance (a): routed generation equals the
    unrouted engine running the member plan, token for token."""
    from repro.launch.serve import build_routed_model
    cfg = get_config("qwen2-0.5b").reduced()
    router, entry = build_routed_model(cfg, "ffn", LengthBuckets((4,)),
                                       max_len=32, log=SILENT)
    routed = ServeEngine(cfg, entry.params, entry.plan, batch_slots=2,
                         max_len=32, precision=entry.precision,
                         router=router)
    prompts = {0: [5, 9, 3], 1: [7, 2, 8, 4, 6, 1]}   # len<=4 / len>4
    for cid, p in prompts.items():
        routed.submit(Request(uid=cid, prompt=p, max_tokens=4))
    outs = {r.uid: r.output for r in routed.run()}
    assert router.requests_by_cluster == {0: 1, 1: 1}
    for cid, p in prompts.items():
        e = router.entry(cid)
        solo = ServeEngine(cfg, e.params, e.plan, batch_slots=2,
                           max_len=32, precision=e.precision)
        solo.submit(Request(uid=0, prompt=p, max_tokens=4))
        assert solo.run()[0].output == outs[cid]


def test_routed_engine_k_executables_and_zero_steady_state_retraces():
    """Acceptance (b): a routed deployment holds exactly K executable
    entries per (backend, bucket) reached by K clusters — even with
    identical plan content — and re-serving the same shapes retraces
    nothing."""
    from repro.launch.serve import build_routed_model
    cfg = tiny_cfg()
    router, entry = build_routed_model(cfg, "ffn", LengthBuckets((6, 12)),
                                       head=("cls", 15), max_len=32,
                                       log=SILENT)
    engine = EncoderServeEngine(cfg, entry.params, entry.plan,
                                target="cls", max_batch=2, max_len=32,
                                router=router)
    # bucket 8 is reached by clusters 0 and 1; bucket 16 by 1 and 2
    lengths = [5, 7, 10, 14]         # (c0,b8) (c1,b8) (c1,b16) (c2,b16)
    uid = 0
    for n in lengths:
        engine.submit(EncoderRequest(uid=uid,
                                     tokens=_req_tokens(cfg, n)))
        uid += 1
        engine.step(force=True)
    s = engine.stats
    assert s["runtime_executables"] == 4   # 2 clusters x 2 buckets
    warm = s["runtime_traces"]
    for n in lengths:                      # steady state: all warm
        engine.submit(EncoderRequest(uid=uid,
                                     tokens=_req_tokens(cfg, n, seed=9)))
        uid += 1
        engine.step(force=True)
    s = engine.stats
    assert s["runtime_traces"] == warm     # zero steady-state retraces
    assert s["runtime_executables"] == 4
    assert dict(router.requests_by_cluster) == {0: 2, 1: 4, 2: 2}


def test_adaptive_http_e2e_with_metrics(adaptive_samp):
    """Acceptance (c): the K=3 deployment served over HTTP — per-request
    traffic routing by content, responses matching the member pipelines,
    and both routing metrics exported at /metrics."""
    samp = adaptive_samp
    fe = samp.serve_http(port=0, batch_slots=4, max_len=16,
                         max_wait=0.005, log=SILENT)
    cases = {0: _req_tokens(samp.cfg, 5, seed=2),
             1: _req_tokens(samp.cfg, 10, seed=2),
             2: _req_tokens(samp.cfg, 14, seed=2)}

    async def scenario(port):
        results = {}
        for cid, toks in cases.items():
            results[cid] = await http_json(
                "127.0.0.1", port, "POST", "/v1/encode", {"tokens": toks})
        metrics = await scrape_metrics("127.0.0.1", port)
        return results, metrics

    async def main():
        await fe.start()
        try:
            return await scenario(fe.port)
        finally:
            await fe.stop()

    results, metrics = asyncio.run(main())
    for cid, toks in cases.items():
        status, _, obj = results[cid]
        assert status == 200
        entry = samp.router.entry(cid)
        pipe_c = samp.pipeline.with_policy(entry.params, entry.plan,
                                           entry.precision)
        batch = {"tokens": np.asarray([toks]),
                 "segments": np.zeros((1, len(toks)), np.int32)}
        np.testing.assert_allclose(np.asarray(obj["logits"]),
                                   pipe_c.predict_logits(batch)[0],
                                   rtol=0, atol=1e-5)
    for c in (0, 1, 2):
        assert f'cluster="{c}"' in metrics
    assert "samp_cluster_requests_total{" in metrics
    assert "samp_active_plans{" in metrics


def test_embedding_kmeans_routes_end_to_end():
    """EmbeddingKMeans fits during calibration, binds the deployment's
    embedding table, and routes at admission."""
    cfg = tiny_cfg()
    eng = SAMPEngine(cfg, float_dtype="float32")
    params = T.init_params(KEY, cfg, eng.float_precision, head=("cls", 3))
    model = EmbeddingKMeans(2, seed=0)
    batches, classes = clustered_synthetic_batches(cfg, model, max_len=16)
    fit_cluster_model(model, params, batches, cfg)
    assert model.fitted
    stats = eng.calibrate(params, batches,
                          clusters=batch_clusters(model, batches,
                                                  batch_classes=classes))
    planset = PlanSet.uniform(_ffn_plan(cfg), range(2))
    router = build_router(cfg, params, planset, stats,
                          cluster_model=model, scheme=eng.scheme,
                          float_plan=eng.float_plan)
    toks = _req_tokens(cfg, 9)
    req = EncoderRequest(uid=0, tokens=toks)
    cid = router.admit(req)
    assert req.cluster == cid
    # host-side admission assignment agrees with the pure-JAX path
    pooled = pooled_embeddings(
        params, {"tokens": np.asarray([toks], np.int32),
                 "segments": np.zeros((1, len(toks)), np.int32)}, cfg)
    assert int(model.assign_embedded(pooled)[0]) == cid
