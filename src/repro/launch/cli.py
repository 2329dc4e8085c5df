"""Shared CLI surface for the serving entrypoints.

``launch/serve.py`` (the synchronous one-shot CLI) and
``launch/server.py`` (the HTTP/SSE front-end) serve the same deployments,
so they must parse the same deployment flags the same way. This module is
the single definition of that surface — ``--arch / --reduced / --task /
--policy / --plan / --clusters / --strategy / --max-latency / --backend /
--mesh / --slots / --max-len / --seed`` — so the two entrypoints cannot
drift. :func:`parse_cluster_model` turns the ``--clusters`` spec string
into a :class:`~repro.adaptive.clusters.ClusterModel`;
:func:`enable_compilation_cache` is the one place a process that serves
places JAX's persistent compilation cache.
"""
from __future__ import annotations

import argparse
import os
import pathlib

#: the checkout this package runs from (``src/repro/launch/cli.py`` -> root)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def add_serving_flags(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """The deployment flags every serving entrypoint shares."""
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's reduced() config (small widths, "
                         "for CPU runs) instead of its published widths")
    ap.add_argument("--task", default=None,
                    help="lm (decode engine) | tnews|iflytek|afqmc|ner "
                         "(encoder engine); default: lm when the arch "
                         "decodes, tnews otherwise")
    ap.add_argument("--policy", default="float",
                    help="float | ffn[K] | full[K]")
    ap.add_argument("--plan", default=None,
                    help="path to a saved PrecisionPlan or PlanSet JSON "
                         "(overrides --policy/--strategy; a PlanSet needs "
                         "--clusters with a matching cluster count)")
    ap.add_argument("--clusters", default=None,
                    help="input-adaptive precision: route requests to "
                         "per-cluster plans. 'length:8,16' (length bins), "
                         "'task:chat,search' (X-SAMP-Traffic-Class "
                         "labels), 'kmeans:3' (embedding k-means). "
                         "Calibration turns cluster-conditional; --policy "
                         "deploys the same plan per cluster (per-cluster "
                         "scales), --plan may name a PlanSet")
    ap.add_argument("--strategy", default=None,
                    choices=("prefix_grid", "greedy", "latency_budget"),
                    help="pick the plan with a search strategy instead of "
                         "--policy")
    ap.add_argument("--max-latency", type=float, default=None,
                    help="latency ceiling (roofline seconds) for "
                         "--strategy latency_budget")
    ap.add_argument("--backend", default="auto",
                    choices=("reference", "fused", "auto"),
                    help="compute backend for quantized blocks: reference "
                         "XLA ops, fused Pallas kernels, or auto (default: "
                         "fused on TPU, reference elsewhere)")
    ap.add_argument("--mesh", default="1,1",
                    help="serving mesh as 'dp,tp' (data-parallel x tensor-"
                         "parallel device counts); 1,1 = unmeshed. Needs "
                         "dp*tp visible devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch slots / encoder micro-batch size")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page; switches the decode caches "
                         "to the paged layout (pages allocated on demand, "
                         "freed on completion/cancel). Required for "
                         "--kv-dtype int8_*")
    ap.add_argument("--kv-dtype", default=None,
                    choices=("float", "int8_per_head", "int8_per_token"),
                    help="KV-cache page scheme for every full-attention "
                         "layer; int8_per_head needs a plan calibrated "
                         "with KV stats, int8_per_token quantizes "
                         "dynamically at decode time. Default: the plan's "
                         "per-layer kv_cache schemes")
    return ap


def resolve_config(args):
    """The served ArchConfig: published widths unless ``--reduced``."""
    from repro.configs import get_config
    cfg = get_config(args.arch)
    return cfg.reduced() if args.reduced else cfg


def compilation_cache_dir(environ=os.environ) -> str:
    """Where JAX's persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the fixed,
    git-ignored ``.jax_cache`` of the checkout. The path is part of every
    cache key, so it never varies from run to run."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on the persistent compilation cache at
    :func:`compilation_cache_dir`; returns the directory. JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so only the fallback is set in
    code. Called by the serving entry points and ``chip_smoke.py``, before
    their first compile."""
    import jax
    path = compilation_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_cluster_model(spec):
    """Parse a ``--clusters`` spec into a ClusterModel (None -> None).

    ``length:8,16`` -> LengthBuckets((8, 16)); ``task:chat,search`` ->
    TaskLabel(("chat", "search")); ``kmeans:3`` -> EmbeddingKMeans(3).
    """
    if spec is None:
        return None
    from repro.adaptive import EmbeddingKMeans, LengthBuckets, TaskLabel
    kind, _, rest = spec.partition(":")
    try:
        if kind == "length":
            return LengthBuckets(tuple(int(x) for x in rest.split(",") if x))
        if kind == "task":
            return TaskLabel(tuple(x for x in rest.split(",") if x))
        if kind == "kmeans":
            return EmbeddingKMeans(int(rest))
    except (ValueError, TypeError) as e:
        raise SystemExit(f"--clusters {spec!r}: {e}")
    raise SystemExit(f"--clusters {spec!r}: unknown model {kind!r}; use "
                     f"length:<edges> | task:<labels> | kmeans:<K>")


def resolve_task(cfg, task):
    """Default/validate ``--task`` against the architecture: ``lm`` needs
    a decode-capable config; encoder-only configs default to ``tnews``."""
    if task is None:
        return "lm" if cfg.supports_decode else "tnews"
    if task == "lm" and not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: pass --task "
                         f"tnews|iflytek|afqmc|ner")
    return task
