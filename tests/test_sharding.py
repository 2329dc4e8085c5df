"""Sharding rules: spec validity for every arch x precision, divisibility
discipline, and an 8-device end-to-end pjit run (subprocess)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.core.plan import PrecisionPlan
from repro.core.precision import make_policy
from repro.distributed.sharding import Rules
from repro.models import transformer as T


class FakeMesh:
    """Just enough Mesh interface for spec computation (no devices)."""
    def __init__(self, shape_dict):
        self.shape = shape_dict
        self.axis_names = tuple(shape_dict)


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if a != "bert-base"])
@pytest.mark.parametrize("policy_name", ["float", "ffn"])
def test_specs_divisible_everywhere(arch, policy_name):
    """Every sharded param dim must divide by its mesh axis size — params
    never rely on GSPMD padding (that's reserved for activations)."""
    cfg = get_config(arch)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = Rules(cfg, mesh)
    policy = make_policy(cfg, policy_name)
    if policy_name == "float":
        params = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg, policy,
                                  dtype=jnp.bfloat16))
    else:
        from repro.launch.dryrun import abstract_stats, quantized_param_specs
        params = quantized_param_specs(cfg, policy)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    specs = jax.tree_util.tree_leaves(
        rules.params_spec(params), is_leaf=lambda x: isinstance(x, P))
    assert len(flat) == len(specs)
    n_sharded = 0
    for (kp, leaf), spec in zip(flat, specs):
        assert len(spec) <= leaf.ndim
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else \
                int(jnp.prod(jnp.asarray([mesh.shape[a] for a in ax])))
            assert dim % size == 0, (jax.tree_util.keystr(kp), leaf.shape,
                                     spec)
            n_sharded += 1
    assert n_sharded > 0          # rules actually shard something


def test_fsdp_shards_big_matrices():
    cfg = get_config("deepseek-coder-33b")
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = Rules(cfg, mesh)
    # attention projection: (stack, d_model, q_dim) -> (None, data, model)
    spec = rules.spec_for("groups/0/layers/0/attn/wq/w", (62, 7168, 7168))
    assert spec == P(None, "data", "model")
    spec_o = rules.spec_for("groups/0/layers/0/attn/wo/w", (62, 7168, 7168))
    assert spec_o == P(None, "model", "data")


def test_expert_sharding_by_divisibility():
    mesh = FakeMesh({"data": 16, "model": 16})
    # dsv2: 160 experts % 16 == 0 -> EP over data
    dsv2 = Rules(get_config("deepseek-v2-236b"), mesh)
    spec = dsv2.spec_for("groups/1/layers/0/ffn/wg/w", (59, 160, 5120, 1536))
    assert spec == P(None, "data", None, "model")
    # mixtral: 8 experts -> FSDP the d_model dim instead
    mix = Rules(get_config("mixtral-8x22b"), mesh)
    spec2 = mix.spec_for("groups/0/layers/0/ffn/wg/w", (56, 8, 6144, 16384))
    assert spec2 == P(None, None, "data", "model")


def test_tied_vs_untied_embedding():
    mesh = FakeMesh({"data": 16, "model": 16})
    tied = Rules(get_config("qwen2-0.5b"), mesh)       # tied -> vocab-parallel
    assert tied.spec_for("embed/tok", (151936, 896)) == P("model", None)
    untied = Rules(get_config("granite-20b"), mesh)
    assert untied.spec_for("embed/tok", (49152, 6144)) == P(None, "model")


def test_quantized_leaf_specs():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = Rules(get_config("qwen2-0.5b"), mesh)
    w = rules.spec_for("groups/0/layers/0/ffn/wg/w/values", (24, 896, 4864))
    assert w == P(None, "data", "model")
    s = rules.spec_for("groups/0/layers/0/ffn/wg/w/scale", (24, 1, 4864))
    assert s == P(None, None, "model")                 # 1-dims unsharded
    zp = rules.spec_for("groups/0/layers/0/ffn/wg/w/zero_point", ())
    assert zp == P()


def test_nondivisible_heads_fall_back_to_replication():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = Rules(get_config("qwen2-0.5b"), mesh)      # kv_dim = 128
    spec = rules.spec_for("groups/0/layers/0/attn/wk/w", (24, 896, 128))
    assert spec == P(None, "data", "model")            # 128 % 16 == 0: fine
    # a truly non-divisible out-dim replicates
    spec2 = rules.spec_for("groups/0/layers/0/attn/wk/w", (24, 896, 56))
    assert spec2 == P(None, "data", None)


@pytest.mark.slow
def test_pjit_train_step_8dev_subprocess(tmp_path):
    """End-to-end: reduced model, 8 host devices, (4, 2) mesh, real pjit
    train step with FSDP+TP rules; loss finite and params stay sharded."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.train import Trainer, TrainConfig, AdamW
        from repro.data import make_task, get_batch

        cfg = get_config("qwen2-0.5b").reduced()
        policy = PrecisionPlan.full_float(cfg.num_layers, "float32")
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        tr = Trainer(cfg, policy, mesh=mesh, optimizer=AdamW(lr=1e-3),
                     tcfg=TrainConfig(steps=2, compute_dtype="float32"))
        state = tr.init_state(jax.random.PRNGKey(0))
        task = make_task("lm", vocab_size=cfg.vocab_size, seq_len=16)
        step = tr.make_step()
        with mesh:
            for i in range(2):
                b = {k: jnp.asarray(v) for k, v in get_batch(task, i, 8).items()}
                p, o, e, m = step(state.params, state.opt_state, None, b)
                from repro.train.trainer import TrainState
                state = TrainState(p, o, e)
        loss = float(m["loss"])
        assert jnp.isfinite(loss), loss
        shards = {len(l.sharding.device_set)
                  for l in jax.tree_util.tree_leaves(state.params)}
        assert max(shards) == 8, shards
        print("OK", loss)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_dryrun_cell_subprocess():
    """The dry-run entry point works end-to-end for one cell."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-0.5b",
         "--shape", "decode_32k", "--mesh", "single", "--force",
         "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root"},
        cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "-> ok" in r.stdout
