"""Builds the system under test for a cell, as the serving launchers build
it, and warms every shape the cell's traffic can reach.

The float weights are the benchmark's own: one jitted call from the seed
fills the parameter tree the program's initializer would return (its
shapes are taken with ``jax.eval_shape``, so the program makes no weight).
The program then calibrates on its 4-batch synthetic stream and applies the
configuration's SAMP plan (``launch.serve.build_model`` after its own
initialisation), and the engine is built with ``launch/server.py``'s
arguments.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any

import jax
import jax.numpy as jnp

import traffic as traffic_mod

#: published keys of a configuration file and the ArchConfig field each
#: must equal, so the file holds the configuration as it is run
WIDTH_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "max_position_embeddings": "max_position",
              "type_vocab_size": "num_segments",
              "rope_theta": "rope_theta",
              "tie_word_embeddings": "tie_embeddings"}


@dataclasses.dataclass
class System:
    cell: Any
    arch: Any                   # the program's ArchConfig
    engine: Any
    max_len: int


def arch_config(config: dict):
    from repro.configs import get_config
    arch = get_config(config["registry"])
    for key, field in WIDTH_KEYS.items():
        if key in config and config[key] != getattr(arch, field):
            raise ValueError(f"{config['name']}: {key}={config[key]} but the "
                             f"program's {config['registry']} has {field}="
                             f"{getattr(arch, field)}")
    return arch


def jax_seed(seed: int) -> int:
    return seed % (2 ** 31)


def _fill(shapes, key):
    """Random float weights in the layout of ``shapes`` (a tree of
    ShapeDtypeStruct), by leaf name: linear weights normal / sqrt(fan_in),
    embedding tables normal * 0.02, norm scales one, biases zero."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "w":
            out.append(jax.random.normal(k, s.shape, s.dtype)
                       / math.sqrt(s.shape[-2]))
        elif name in ("tok", "pos", "seg"):
            out.append(jax.random.normal(k, s.shape, s.dtype) * 0.02)
        elif name == "scale":
            out.append(jnp.ones(s.shape, s.dtype))
        elif name in ("b", "bias"):
            out.append(jnp.zeros(s.shape, s.dtype))
        else:
            raise ValueError(f"no initialiser for parameter leaf "
                             f"{jax.tree_util.keystr(path)}")
    return jax.tree_util.tree_unflatten(treedef, out)


def float_params(config: dict, arch, seed: int):
    """The benchmark's float32 weights for ``seed``, made on the device in
    one jitted call."""
    from repro.core.precision import EncoderPolicy
    from repro.models import transformer as T
    head = tuple(config["head"]) if "head" in config else None
    shapes = jax.eval_shape(lambda: T.init_params(
        jax.random.PRNGKey(0), arch,
        EncoderPolicy.full_float(arch.num_layers, "float32"), head=head))
    key = jax.random.PRNGKey(jax_seed(seed))
    return jax.jit(lambda k: _fill(shapes, k))(key)


def precision_plan(config: dict, arch):
    from repro.core.plan import plan_from_policy
    from repro.core.precision import make_policy
    from repro.core.samp import int8_dataflow_variant
    plan = plan_from_policy(make_policy(arch, config["plan"]["policy"]))
    if config["plan"].get("dataflow") == "int8":
        plan = int8_dataflow_variant(plan)
    return plan


def quantize(config: dict, arch, params, seed: int):
    """The program's PTQ: synthetic calibration, then the plan applied."""
    from repro.core.calibration import synthetic_calibration_batches
    from repro.core.samp import SAMPEngine
    precision = precision_plan(config, arch)
    eng = SAMPEngine(arch, float_dtype="float32")
    batches = synthetic_calibration_batches(arch, seed=jax_seed(seed))
    stats = eng.calibrate(params, batches, precision=precision)
    qparams, plan = eng.apply(params, stats, precision)
    return qparams, plan, precision


def decode_max_len(config: dict, traffic: dict) -> int:
    """Longest prompt plus output of the mix, rounded up to a page."""
    page = config["engine"]["page_size"]
    return -(-traffic_mod.longest(traffic) // page) * page


def build(cell, seed: int, log=print) -> System:
    from repro.toolkit.registry import get_target
    config = cell.config
    arch = arch_config(config)
    t = time.perf_counter()
    params = jax.block_until_ready(float_params(config, arch, seed))
    log(f"[setup] float weights in {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    qparams, plan, precision = quantize(config, arch, params, seed)
    jax.block_until_ready(qparams)
    log(f"[setup] calibrated and quantized in {time.perf_counter() - t:.3f}s")
    del params
    eng_cfg = config["engine"]
    dtype = jnp.dtype(config["compute_dtype"])
    if cell.kind == "encoder":
        from repro.serve import EncoderServeEngine
        max_len = eng_cfg["max_len"]
        engine = EncoderServeEngine(
            arch, qparams, plan, target=get_target(config["head"][0]),
            max_batch=eng_cfg["max_batch"], max_wait=eng_cfg["max_wait_s"],
            max_len=max_len, compute_dtype=dtype, backend=config["backend"])
    else:
        from repro.serve import ServeEngine
        max_len = decode_max_len(config, cell.traffic)
        engine = ServeEngine(
            arch, qparams, plan, batch_slots=eng_cfg["slots"],
            max_len=max_len, seed=jax_seed(seed), cache_dtype=dtype,
            compute_dtype=dtype, backend=config["backend"],
            page_size=eng_cfg["page_size"], kv_cache=eng_cfg["kv_cache"],
            precision=precision)
    log(f"[setup] {config['name']}: {precision.describe()} on "
        f"{engine.runtime.backend.describe()}, max_len {max_len}")
    return System(cell=cell, arch=arch, engine=engine, max_len=max_len)


def encoder_shapes(system: System) -> list:
    """Every (rows, length) the engine can run under the cell's traffic: the
    batcher's length bucket of every length the mix holds, by every batch
    size the micro-batcher can flush, deduplicated by the runtime's own
    batch bucketing."""
    from repro.serve.runtime import bucket_size
    eng = system.engine
    lengths = sorted({eng.batcher.bucket(n)
                      for lo, hi in traffic_mod.length_ranges(
                          system.cell.traffic)
                      for n in range(lo, hi + 1)})
    rows = sorted({bucket_size(b, eng.runtime.min_batch)
                   for b in range(1, eng.batcher.max_batch + 1)})
    return [(b, n) for n in lengths for b in rows]


def warm(system: System, log=print) -> None:
    """Run every executable the window can reach once, so that nothing
    compiles inside it."""
    eng = system.engine
    t = time.perf_counter()
    if system.cell.kind == "encoder":
        from repro.serve import EncoderRequest
        shapes = encoder_shapes(system)
        segs = [0] if system.arch.num_segments else None
        for b, n in shapes:
            # one micro-batch of ``b`` rows in the ``n`` bucket, through the
            # engine's own step: pad, dispatch, fetch and the head's decision
            for uid in range(b):
                eng.submit(EncoderRequest(uid=-1 - uid, tokens=[1] * n,
                                          segments=None if segs is None
                                          else segs * n))
            eng.run()
        log(f"[setup] warmed {len(shapes)} encoder shapes "
            f"(rows x length) in {time.perf_counter() - t:.3f}s: {shapes}")
        return
    from repro.serve import Request
    # two short requests: admission (slot reset), the decode step, and the
    # page invalidation that follows a retirement
    for uid in (-1, -2):
        eng.submit(Request(uid=uid, prompt=[1, 2], max_tokens=2))
    eng.run()
    eng.step()          # an idle tick invalidates the retired pages
    log(f"[setup] warmed the decode step ({eng.slots} slots, max_len "
        f"{system.max_len}) in {time.perf_counter() - t:.3f}s")
