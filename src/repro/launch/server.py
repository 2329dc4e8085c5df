"""HTTP/SSE serving entrypoint — the asyncio front-end over the engines.

    # encoder serving (JSON request/response) on the golden plan, which
    # is calibrated for the 4-layer reduced() widths
    PYTHONPATH=src python -m repro.launch.server --arch bert-base \
        --reduced --task tnews --plan tests/data/golden_plan.json --port 8080

    # a decode-capable arch mounts BOTH endpoints: /v1/encode for the
    # encoder task and /v1/generate for SSE token streaming
    PYTHONPATH=src python -m repro.launch.server --arch qwen2-0.5b \
        --task tnews --policy ffn --port 8080

    # input-adaptive precision: per-cluster plans, routed per request
    # (docs/adaptive-precision.md; tag requests with X-SAMP-Traffic-Class
    # or the 'traffic_class' JSON field for task: routing)
    ... --clusters length:8,16

    curl -s localhost:8080/v1/encode -d '{"tokens": [2, 17, 9, 41]}'
    curl -sN localhost:8080/v1/generate -d '{"prompt": [2, 17], "max_tokens": 8}'
    curl -s localhost:8080/metrics

Builds the model exactly like ``launch/serve.py`` (same shared flag
surface — ``launch/cli.py``), wraps the engine(s) in
:class:`~repro.serve.frontend.HTTPFrontend`, and serves until SIGTERM /
SIGINT, which triggers a graceful drain (stop admitting with 503, finish
in-flight requests, exit). ``--port 0`` binds an ephemeral port and
prints it — CI's smoke uses that. See docs/http-serving.md for the
endpoint contracts, backpressure semantics, and the metrics catalog.
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.data.pipeline import make_task
from repro.launch.cli import (add_serving_flags, enable_compilation_cache,
                              parse_cluster_model, resolve_config,
                              resolve_task)
from repro.launch.mesh import make_serving_mesh
from repro.launch.serve import build_model, build_routed_model
from repro.serve import EncoderServeEngine, ServeEngine
from repro.serve.frontend import HTTPFrontend
from repro.toolkit.registry import get_target
from repro.toolkit.targets import TARGET_FOR_TASK_KIND


def build_frontend(args, *, log=print) -> HTTPFrontend:
    """Build engine(s) for the requested deployment and mount them.

    ``--task lm`` mounts the decode engine only. An encoder task on a
    decode-capable arch mounts BOTH engines over one param tree (the cls
    head rides next to the tied-embedding lm head), so a single server
    answers /v1/encode and /v1/generate.
    """
    cfg = resolve_config(args)
    task_name = resolve_task(cfg, args.task)
    mesh = make_serving_mesh(args.mesh)
    cluster_model = parse_cluster_model(args.clusters)
    encoder = decode = None
    decode_router = None
    if task_name == "lm":
        if cluster_model is not None:
            decode_router, entry = build_routed_model(
                cfg, args.policy, cluster_model, seed=args.seed,
                plan_file=args.plan, max_len=args.max_len, log=log)
            params, plan, precision = (entry.params, entry.plan,
                                       entry.precision)
        else:
            params, plan, precision = build_model(
                cfg, args.policy, seed=args.seed, plan_file=args.plan,
                strategy=args.strategy, max_latency=args.max_latency,
                log=log)
    else:
        task = make_task(task_name, vocab_size=cfg.vocab_size,
                         seq_len=args.max_len)
        spec = get_target(TARGET_FOR_TASK_KIND[task.kind])
        head_kind = "ner" if spec.token_level else "cls"
        head = (head_kind, max(task.n_classes, 1))
        router = None
        if cluster_model is not None:
            # a PlanRouter binds to ONE runtime: route the encoder (the
            # served task); a co-mounted decode engine serves the default
            # member unrouted
            router, entry = build_routed_model(
                cfg, args.policy, cluster_model, seed=args.seed, head=head,
                plan_file=args.plan, max_len=args.max_len, log=log)
            params, plan, precision = (entry.params, entry.plan,
                                       entry.precision)
        else:
            params, plan, precision = build_model(
                cfg, args.policy, seed=args.seed, head=head,
                plan_file=args.plan, strategy=args.strategy,
                max_latency=args.max_latency, log=log)
        encoder = EncoderServeEngine(cfg, params, plan, target=spec,
                                     max_batch=args.slots,
                                     max_wait=args.max_wait,
                                     max_len=args.max_len,
                                     backend=args.backend, mesh=mesh,
                                     router=router)
    if cfg.supports_decode:
        decode = ServeEngine(cfg, params, plan, batch_slots=args.slots,
                             max_len=args.max_len, seed=args.seed,
                             cache_dtype=jnp.float32,
                             backend=args.backend, mesh=mesh,
                             page_size=args.page_size,
                             kv_cache=args.kv_dtype, precision=precision,
                             router=decode_router)
    return HTTPFrontend(encoder=encoder, decode=decode, host=args.host,
                        port=args.port, max_pending=args.max_pending,
                        default_deadline_s=args.deadline_s, log=log)


def main():
    ap = add_serving_flags(argparse.ArgumentParser())
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 binds an ephemeral port (printed at startup)")
    ap.add_argument("--max-pending", type=int, default=64,
                    help="admission bound on in-flight requests; overflow "
                         "answers 429 + Retry-After")
    ap.add_argument("--max-wait", type=float, default=0.005,
                    help="encoder micro-batch ageing window (seconds)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="default per-request deadline when the request "
                         "states no deadline_ms (None = unbounded)")
    args = ap.parse_args()
    enable_compilation_cache()
    frontend = build_frontend(args)
    frontend.run_forever()


if __name__ == "__main__":
    main()
