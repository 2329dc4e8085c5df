"""Mesh-aware serving: quantized-param sharding specs, executable-cache
topology keying, shard-aware fused-backend declines, and the end-to-end
acceptance run — a 1xN and Nx1 host-mesh serve must reproduce the unmeshed
runtime's logits for the golden plan (subprocess: the host needs >1 device,
which must be forced before jax initializes)."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.core.precision import make_policy
from repro.core.quantize import QuantizedTensor
from repro.distributed.sharding import Rules, mesh_fingerprint
from repro.kernels.backend import FusedBackend, get_backend
from repro.models import transformer as T
from repro.serve import Runtime


class FakeMesh:
    """Just enough Mesh interface for spec/key computation (no devices)."""
    def __init__(self, shape_dict):
        self.shape = shape_dict
        self.axis_names = tuple(shape_dict)


def tiny_bert(num_layers=4):
    return get_config("bert-base").reduced().replace(num_layers=num_layers)


# ---------------------------------------------------------------------------
# quantized-param sharding rules
# ---------------------------------------------------------------------------


def test_mesh_fingerprint():
    assert mesh_fingerprint(None) == "unmeshed"
    m12 = FakeMesh({"data": 1, "model": 2})
    m21 = FakeMesh({"data": 2, "model": 1})
    assert mesh_fingerprint(m12) == "data=1,model=2"
    assert mesh_fingerprint(m12) != mesh_fingerprint(m21)
    assert mesh_fingerprint(FakeMesh({"data": 1, "model": 2})) == \
        mesh_fingerprint(m12)               # same topology, same identity


def test_quantized_scales_shard_with_their_weights():
    """Acceptance (a): every per-channel scale leaf must carry the SAME
    mesh axis on the same dim as its weight's values leaf; broadcast
    (size-1) scale dims and zero-points must replicate."""
    from repro.launch.dryrun import quantized_param_specs
    cfg = get_config("qwen2-0.5b")
    mesh = FakeMesh({"data": 4, "model": 4})
    rules = Rules(cfg, mesh, fsdp=False)
    qparams = quantized_param_specs(cfg, make_policy(cfg, "full"))
    flat, _ = jax.tree_util.tree_flatten_with_path(qparams)
    specs = {jax.tree_util.keystr(kp): rules.spec_for(
        _path(kp), leaf.shape) for kp, leaf in flat}
    shapes = {jax.tree_util.keystr(kp): leaf.shape for kp, leaf in flat}
    checked = 0
    for key, spec in specs.items():
        if not key.endswith(".values"):
            continue
        skey = key[: -len(".values")] + ".scale"
        if skey not in specs:
            continue
        w_spec, s_spec = tuple(spec), tuple(specs[skey])
        s_shape = shapes[skey]
        pad = (None,) * (len(s_shape) - len(s_spec))
        s_spec = s_spec + pad
        w_spec = w_spec + (None,) * (len(shapes[key]) - len(w_spec))
        for d, (ws, ss) in enumerate(zip(w_spec, s_spec)):
            if s_shape[d] == 1:
                assert ss is None, (key, d, s_spec)   # broadcast: replicate
            else:
                assert ss == ws, (key, d, w_spec, s_spec)
        checked += 1
    assert checked > 0


def test_batch_spec_and_dp_size():
    cfg = tiny_bert()
    rules = Rules(cfg, FakeMesh({"data": 4, "model": 2}), fsdp=False)
    assert rules.dp_size == 4
    spec = rules.batch_spec({"tokens": jax.ShapeDtypeStruct((8, 16),
                                                            jnp.int32),
                             "lengths": jax.ShapeDtypeStruct((8,),
                                                             jnp.int32)})
    assert spec["tokens"] == P(("data",), None)
    assert spec["lengths"] == P(("data",))
    ragged = rules.batch_spec({"tokens": jax.ShapeDtypeStruct((6, 16),
                                                              jnp.int32)})
    assert ragged["tokens"] == P(None)          # 6 % 4 != 0: replicate


# ---------------------------------------------------------------------------
# executable-cache topology keying
# ---------------------------------------------------------------------------


def test_runtime_cache_key_never_collides_across_meshes():
    """Acceptance (b): the same plan on different mesh topologies (and
    unmeshed) must occupy distinct executable-cache keys even when the
    runtimes share one cache."""
    cfg = tiny_bert(2)
    policy = make_policy(cfg, "float")
    plan = T.build_plan(cfg, policy)
    rt = Runtime(cfg, plan, compute_dtype=jnp.float32)
    sib12 = rt.share(plan, mesh=FakeMesh({"data": 1, "model": 2}))
    sib21 = rt.share(plan, mesh=FakeMesh({"data": 2, "model": 1}))
    keys = {rt._plan_key, sib12._plan_key, sib21._plan_key}
    assert len(keys) == 3
    assert sib12._exe is rt._exe and sib21._exe is rt._exe
    # share() inherits the mesh by default; None gets an unmeshed sibling
    assert sib12.share(plan)._plan_key == sib12._plan_key
    assert sib12.share(plan, mesh=None)._plan_key == rt._plan_key


def test_meshed_bucket_rounds_to_dp_multiples():
    cfg = tiny_bert(2)
    plan = T.build_plan(cfg, make_policy(cfg, "float"))
    rt = Runtime(cfg, plan, mesh=FakeMesh({"data": 3, "model": 1}))
    assert rt._dp == 3
    # encode() computes Bb = pow2-bucket rounded up to a dp multiple;
    # replicate that arithmetic here for a non-power-of-two dp size
    from repro.serve.runtime import bucket_size
    # the pow2 bucket comes first, THEN the dp rounding (3 -> 4 -> 6)
    for B, want in ((1, 3), (2, 3), (3, 6), (4, 6), (5, 9)):
        Bb = bucket_size(B, rt.min_batch)
        if Bb % rt._dp:
            Bb = -(-Bb // rt._dp) * rt._dp
        assert Bb == want and Bb % 3 == 0, (B, Bb)


# ---------------------------------------------------------------------------
# shard-aware fused-backend declines
# ---------------------------------------------------------------------------


def test_fused_backend_declines_sub_tile_shards():
    """Mosaic kernels cannot be partitioned by the compiler: under TP the
    fused backend declines every op, however wide the per-device shard
    (reference runs it, GSPMD-partitioned); on a pure data-parallel mesh
    the runtime runs each step per device and keeps the unmeshed backend,
    kernels and all."""
    fused = get_backend("fused")
    T2 = 256
    w = QuantizedTensor(jnp.zeros((T2, T2), jnp.int8),
                        jnp.ones((1, T2)), None)
    x = jnp.zeros((4, T2), jnp.float32)
    tp = fused.with_mesh(FakeMesh({"data": 1, "model": 2}))
    assert isinstance(tp, FusedBackend)
    assert tp.linear(x, {"w": w}) is None               # declined
    assert fused.linear(x, {"w": w}) is not None        # with_mesh copies
    # the label says no kernel will run
    assert tp.describe() == "fused[declined: tensor-parallel mesh]"
    cfg = tiny_bert(2)
    plan = T.build_plan(cfg, make_policy(cfg, "float"))
    rt_tp = Runtime(cfg, plan, backend="fused",
                    mesh=FakeMesh({"data": 2, "model": 2}))
    assert not rt_tp.per_device and rt_tp.shards == 1
    assert rt_tp.backend.describe() == "fused[declined: tensor-parallel mesh]"
    rt_dp = Runtime(cfg, plan, backend="fused",
                    mesh=FakeMesh({"data": 3, "model": 1}))
    assert rt_dp.per_device and rt_dp.shards == 3
    assert rt_dp.backend.describe() == "fused"
    assert rt_dp.backend.linear(x, {"w": w}) is not None
    # an unmeshed sibling of the TP runtime gets its kernels back
    assert rt_tp.share(plan, mesh=None).backend.describe() == "fused"
    # the reference backend is sharding-oblivious: with_mesh is identity
    ref = get_backend("reference")
    assert ref.with_mesh(FakeMesh({"data": 8, "model": 8})) is ref


# ---------------------------------------------------------------------------
# acceptance: meshed serve == unmeshed serve (2 host devices, subprocess)
# ---------------------------------------------------------------------------


def test_host_mesh_serve_matches_unmeshed_golden_plan(tmp_path):
    """1xN (TP) and Nx1 (DP) host-mesh serve runs reproduce the unmeshed
    runtime's logits for the golden plan; the shared executable cache takes
    one entry per topology; sharded calibration reduces to the same stats
    as unsharded. Subprocess: the host device count must be forced before
    jax initializes."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core.calibration import synthetic_calibration_batches
        from repro.core.plan import PrecisionPlan
        from repro.core.samp import SAMPEngine
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as T
        from repro.quant import ptq
        from repro.serve import EncoderRequest, EncoderServeEngine, Runtime

        cfg = get_config("bert-base").reduced().replace(num_layers=4)
        eng = SAMPEngine(cfg, float_dtype="float32")
        params = T.init_params(jax.random.PRNGKey(0), cfg,
                               eng.float_policy, head=("cls", 5))
        golden = PrecisionPlan.load("tests/data/golden_plan.json")
        batches = synthetic_calibration_batches(cfg, num_batches=2, seed=0)
        stats = eng.calibrate(params, batches, precision=golden)

        # sharded calibration == unsharded: batches placed over the data
        # axis reduce to the same amax values (observers are global maxes).
        # The max itself is exact, but the sharded program is compiled
        # separately and may order the float adds of the activations it
        # maxes over differently, so allow 16 float32 ulps (the softmax
        # amax of layer 2 moves by 7).
        mesh_dp = make_serving_mesh("2,1")
        sh = NamedSharding(mesh_dp, P("data"))
        sharded = [{k: jax.device_put(jnp.asarray(v), sh)
                    for k, v in b.items()} for b in batches]
        stats_sh = eng.calibrate(params, sharded, precision=golden)
        for layer, sites in stats.items():
            for site, amax in sites.items():
                got = stats_sh[layer][site]
                np.testing.assert_allclose(
                    got, amax, rtol=16 * np.finfo(np.float32).eps, atol=0,
                    err_msg=f"{layer}/{site}")

        qparams, qplan = eng.apply(params, stats, golden)
        head = lambda p, h: T.apply_head(h, p, "cls")
        rng = np.random.default_rng(0)
        toks = rng.integers(1, cfg.vocab_size, size=(3, 12)).astype(np.int32)
        lengths = np.asarray([5, 12, 9], np.int32)
        inputs = {"tokens": toks, "segments": np.zeros_like(toks)}

        rt0 = Runtime(cfg, qplan, precision=golden, head=head)
        ref = rt0.encode(qparams, inputs, lengths)

        # Nx1 = pure DP: per-row compute is untouched, and each device runs
        # the unmeshed program at its share of the batch (2 of the 4 bucket
        # rows) -> bit-identical to that program. XLA:CPU compiles float
        # dots differently for 2 and 4 rows, so the 4-row unmeshed logits
        # differ from both in the last bits.
        rt_dp = rt0.share(qplan, precision=golden, mesh=mesh_dp)
        rt_half = Runtime(cfg, qplan, precision=golden, head=head,
                          min_batch=2)
        per_device = np.concatenate([
            rt_half.encode(qparams, {k: v[a:a + 2] for k, v in
                                     inputs.items()}, lengths[a:a + 2])
            for a in (0, 2)])
        np.testing.assert_array_equal(
            rt_dp.encode(qparams, inputs, lengths), per_device)
        # 1xN = TP: row-parallel psums reorder float adds -> allclose
        rt_tp = rt0.share(qplan, precision=golden,
                          mesh=make_serving_mesh("1,2"))
        np.testing.assert_allclose(
            rt_tp.encode(qparams, inputs, lengths), ref,
            rtol=1e-5, atol=1e-6)

        # one shared cache, one entry + one trace per topology: no collision
        s = rt0.stats
        assert s["traces"] == s["executables"] == 3, s

        # and the engine path: a meshed EncoderServeEngine serves the same
        # predictions as the unmeshed runtime computes
        server = EncoderServeEngine(cfg, qparams, qplan, target="cls",
                                    compute_dtype=jnp.float32,
                                    mesh=mesh_dp, max_batch=4)
        for i in range(3):
            server.submit(EncoderRequest(
                uid=i, tokens=[int(t) for t in toks[i, :lengths[i]]]))
        done = {r.uid: r for r in server.run()}
        for i in range(3):
            assert int(done[i].prediction) == int(ref[i].argmax()), i
        print("OK")
    """)
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env.pop("XLA_FLAGS", None)          # the script sets its own
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(repo))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.parametrize("backend", ["reference", "fused"])
def test_host_mesh_decode_matches_per_device_decode(backend):
    """Nx1 (DP) decode runs each step per device, each device holding its
    own share of the paged int8 KV pool: a 2-slot engine on 2 host devices
    must give every request the tokens, and the logits behind each, of a
    1-slot unmeshed engine, bit for bit. Subprocess: the host device count
    must be forced before jax initializes."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        import numpy as np
        from repro.configs import get_config
        from repro.core.precision import EncoderPolicy
        from repro.launch.mesh import make_serving_mesh
        from repro.models import transformer as T
        from repro.serve import Request, ServeEngine

        cfg = get_config("qwen2-0.5b").reduced().replace(num_layers=2)
        policy = EncoderPolicy.full_float(cfg.num_layers, "float32")
        plan = T.build_plan(cfg, policy)
        params = T.init_params(jax.random.PRNGKey(0), cfg, policy)
        prompts = [[2, 17, 9], [5, 40, 3, 3, 8], [11, 3, 7, 1], [23, 8]]

        def serve(slots, mesh=None):
            eng = ServeEngine(cfg, params, plan, batch_slots=slots,
                              max_len=32, backend="{backend}", mesh=mesh,
                              page_size=4, kv_cache="int8_per_token")
            reqs = [Request(uid=i, prompt=p, max_tokens=6)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            inner, rows = eng._decode, {{}}

            def record(params, caches, tokens, pos, active, pages=None):
                held = {{s: (r.uid, int(eng.sched.cursor[s]))
                        for s, r in enumerate(eng.sched.active)
                        if r is not None}}
                logits, caches = inner(params, caches, tokens, pos, active,
                                       pages)
                for s, key in held.items():
                    if bool(active[s]):
                        rows[key] = np.asarray(logits[s])
                return logits, caches
            eng._decode = record
            eng.run()
            return rows, {{r.uid: r.output for r in reqs}}, eng

        rows1, toks1, _ = serve(1)
        rows2, toks2, eng = serve(2, make_serving_mesh("2,1"))
        assert eng.runtime.per_device and eng.pool.shard_of(1) == 1
        assert toks2 == toks1, (toks2, toks1)
        assert rows2.keys() == rows1.keys()
        for key in rows1:
            np.testing.assert_array_equal(rows2[key], rows1[key],
                                          err_msg=str(key))
        print("OK", len(rows1))
    """)
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"))
    env.pop("XLA_FLAGS", None)          # the script sets its own
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=str(repo))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def _path(kp) -> str:
    from repro.distributed.sharding import _path_str
    return _path_str(kp)
