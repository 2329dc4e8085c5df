import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST run before any other import (jax locks device count on first init).
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell with
ShapeDtypeStruct inputs and record the roofline raw material.

For each cell this script:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. resolves sharding rules (repro.distributed.sharding.Rules),
  3. lowers the cell's step function (train_step / prefill / serve_step)
     against abstract inputs — no arrays are ever allocated,
  4. ``.compile()``s it (this is the proof the distribution config is
     coherent: sharding mismatches, OOM-at-compile and unsupported
     collectives all fail here),
  5. records ``memory_analysis()``, ``cost_analysis()`` and the per-type
     collective bytes parsed from the post-SPMD optimized HLO,
  6. appends the cell's record to results/dryrun/<cell>.json (incremental:
     re-runs skip cells that already have results unless --force).

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --all --mesh single --policy ffn8
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.core.plan import PrecisionPlan
from repro.core.precision import make_policy
from repro.distributed.sharding import Rules
from repro.launch.hlo_cost import analyze_hlo
from repro.launch import shapes as SH
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.quant import ptq
from repro.train.optimizer import AdamW
from repro.train.trainer import TrainConfig, Trainer

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2}


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the post-SPMD HLO
    (per-device numbers — SPMD-partitioned shapes are local shapes)."""
    out = {c: {"bytes": 0, "count": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # op name appears after '=' as e.g. 'bf16[128,512]{1,0} all-gather('
        for c in _COLLECTIVES:
            if f" {c}(" in stripped or f" {c}-start(" in stripped:
                m = _SHAPE_RE.search(stripped)
                if not m:
                    continue
                dtype, dims = m.group(1), m.group(2)
                nbytes = _DTYPE_BYTES.get(dtype, 4)
                numel = 1
                for d in dims.split(","):
                    if d:
                        numel *= int(d)
                out[c]["bytes"] += numel * nbytes
                out[c]["count"] += 1
                break
    return out


def abstract_stats(cfg) -> dict:
    """Placeholder per-layer amax stats (value 1.0) — scale values don't
    affect lowering/compile, only numerics."""
    sites = ("attn_in", "attn_out", "q", "k", "p", "v", "q_lat", "c_kv",
             "ffn_in", "ffn_hidden", "ffn_in_e", "shared_ffn_in",
             "shared_ffn_hidden", "rec_in", "rec_gate_in", "rec_out",
             "blk_in", "blk_conv_in", "blk_hidden", "qkv_in", "xm")
    return {f"layer{i}": {s: 1.0 for s in sites}
            for i in range(cfg.num_layers)}


def quantized_param_specs(cfg, policy, param_dtype=jnp.bfloat16):
    """Abstract quantized params: eval_shape the PTQ transform itself."""
    def build():
        params = T.init_params(jax.random.PRNGKey(0), cfg,
                               PrecisionPlan.full_float(cfg.num_layers),
                               dtype=param_dtype)
        qp, _ = ptq.apply_plan(params, cfg, policy, abstract_stats(cfg))
        return qp
    return jax.eval_shape(build)


def build_cell(arch: str, shape_name: str, mesh, policy_name: str = "float",
               param_dtype=jnp.bfloat16):
    """-> (jitted-with-shardings fn, example_args (SDS pytrees)).
    Raises ValueError for skipped cells."""
    cfg = get_config(arch)
    cell = SH.SHAPES[shape_name]
    ok, why = SH.cell_supported(cfg, shape_name)
    if not ok:
        raise ValueError(f"SKIP {arch}/{shape_name}: {why}")
    policy = make_policy(cfg, policy_name)
    plan = T.build_plan(cfg, policy)
    # FSDP (ZeRO-3) for training only: a serving step must not all-gather
    # its weights every token — inference weights shard over 'model' and
    # replicate over 'data' (classic TP serving layout)
    rules = Rules(cfg, mesh, fsdp=(cell.kind == "train"))
    scheme = T.QuantScheme()
    head = None

    if policy_name == "float":
        params_sds = SH.params_specs(cfg, policy, param_dtype, head=head)
    else:
        params_sds = quantized_param_specs(cfg, policy, param_dtype)
    params_sh = rules.params_sharding(params_sds)
    batch_sds = SH.batch_specs(cfg, cell)
    batch_sh = jax.tree_util.tree_map(
        lambda s: jax.NamedSharding(mesh, s), rules.batch_spec(batch_sds))

    if cell.kind == "train":
        trainer = Trainer(cfg, policy, mesh=mesh,
                          optimizer=AdamW(lr=1e-4),
                          tcfg=TrainConfig(remat=True,
                                           compute_dtype="bfloat16"),
                          scheme=scheme)
        step = trainer.make_step(jit=False)
        opt_sds = jax.eval_shape(trainer.optimizer.init, params_sds)
        opt_sh = jax.tree_util.tree_map(
            lambda s: jax.NamedSharding(mesh, s),
            rules.params_spec(opt_sds))
        fn = jax.jit(step,
                     in_shardings=(params_sh, opt_sh, None, batch_sh),
                     out_shardings=(params_sh, opt_sh, None, None))
        args = (params_sds, opt_sds, None, batch_sds)
        return fn, args

    caches_sds = SH.cache_specs(cfg, plan, cell)
    caches_sh = jax.tree_util.tree_map(
        lambda s: jax.NamedSharding(mesh, s), rules.cache_spec(caches_sds),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    if cell.kind == "prefill":
        pchunk = rules.attn_chunk(cell.global_batch, cell.seq_len,
                                  cfg.num_heads)

        def step(params, batch, caches):
            return SH.prefill_step(params, batch, caches, cfg, plan, scheme,
                                   constrain=rules, chunk=pchunk)
        use_caches = cfg.supports_decode
        fn = jax.jit(step, in_shardings=(
            params_sh, batch_sh, caches_sh if use_caches else None))
        args = (params_sds, batch_sds, caches_sds if use_caches else None)
        return fn, args

    # decode
    def step(params, tokens, caches, pos):
        return T.decode_step(params, tokens, caches, pos, cfg, plan, scheme,
                             constrain=rules)
    tok_sds = batch_sds["tokens"]
    tok_sh = jax.NamedSharding(mesh, rules.batch_spec({"t": tok_sds})["t"])
    fn = jax.jit(step, in_shardings=(params_sh, tok_sh, caches_sh, None))
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    args = (params_sds, tok_sds, caches_sds, pos_sds)
    return fn, args


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             policy_name: str = "float", out_dir: str = RESULTS_DIR,
             force: bool = False) -> dict:
    cell_id = f"{arch}__{shape_name}__{mesh_kind}__{policy_name}"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "policy": policy_name, "status": "error"}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        fn, args = build_cell(arch, shape_name, mesh, policy_name)
        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        # trip-count-aware re-analysis (XLA cost_analysis counts each while
        # body once — see repro.launch.hlo_cost)
        corrected = analyze_hlo(hlo)
        record.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            num_devices=mesh.devices.size,
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
            },
            cost={"flops": cost.get("flops", 0.0),
                  "bytes accessed": cost.get("bytes accessed", 0.0),
                  "transcendentals": cost.get("transcendentals", 0.0)},
            corrected={"flops": corrected["flops"],
                       "bytes": corrected["bytes"],
                       "collective_bytes": corrected["collective_bytes"]},
            collectives=corrected["collectives"],
            hlo_ops=len(hlo.splitlines()),
        )
    except ValueError as e:
        if str(e).startswith("SKIP"):
            record.update(status="skip", reason=str(e))
        else:
            record.update(status="error", error=str(e),
                          trace=traceback.format_exc()[-2000:])
    except Exception as e:  # compile failures are data, not crashes
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
    record["wall_s"] = round(time.time() - t0, 1)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SH.SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--policy", default="float")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) cell")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = [a for a in ARCH_IDS if a != "bert-base"] \
        if args.arch is None else [args.arch]
    shapes = list(SH.SHAPES) if args.shape is None else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if not args.all and args.arch is None:
        ap.error("pass --arch or --all")

    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_cell(arch, shape, mk, args.policy, args.out,
                               args.force)
                flops = rec.get("cost", {}).get("flops", 0)
                print(f"{arch:22s} {shape:12s} {mk:6s} {args.policy:6s} "
                      f"-> {rec['status']:5s} "
                      f"flops/dev={flops:.3e} wall={rec.get('wall_s')}s"
                      + (f"  ({rec.get('error', rec.get('reason', ''))})"
                         if rec["status"] != "ok" else ""),
                      flush=True)


if __name__ == "__main__":
    main()
