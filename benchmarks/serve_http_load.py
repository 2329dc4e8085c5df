"""HTTP load generator for the serving front-end (stdlib asyncio only).

    # against a running launch/server.py
    PYTHONPATH=src python benchmarks/serve_http_load.py --port 8080 \
        --mode encode --requests 64 --concurrency 8

Drives ``POST /v1/encode`` (JSON) or ``POST /v1/generate`` (SSE) with a
bounded-concurrency open-loop client, records client-side latency into
the same histogram buckets the server exports at ``/metrics``
(``repro.serve.metrics.LATENCY_BUCKETS``), and counts 429/503 rejections
so the admission controller's behaviour shows up as a *rate*, not an
error log. The tests reuse the client helpers to talk to in-process
front-ends.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.serve.frontend.protocol import parse_sse
from repro.serve.metrics import latency_summary


async def http_request(host: str, port: int, method: str, path: str,
                       payload=None) -> tuple[int, dict, bytes]:
    """One request over a fresh connection (the server is
    Connection: close); returns (status, headers, raw body)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = b"" if payload is None else json.dumps(payload).encode("utf-8")
        head = (f"{method} {path} HTTP/1.1\r\n"
                f"Host: {host}:{port}\r\n"
                f"Content-Length: {len(body)}\r\n"
                f"Connection: close\r\n\r\n")
        writer.write(head.encode("latin1") + body)
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass
    header_blob, _, rest = raw.partition(b"\r\n\r\n")
    lines = header_blob.decode("latin1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for line in lines[1:]:
        k, _, v = line.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, rest


async def http_json(host: str, port: int, method: str, path: str,
                    payload=None) -> tuple[int, dict, dict]:
    """JSON request/response; returns (status, headers, decoded body)."""
    status, headers, body = await http_request(host, port, method, path,
                                               payload)
    obj = json.loads(body.decode("utf-8")) if body else {}
    return status, headers, obj


async def http_sse(host: str, port: int, path: str,
                   payload) -> tuple[int, dict, list]:
    """SSE request; returns (status, headers, [(event, data), ...]).
    Non-200 answers decode the JSON error body into a single
    ``("error", ...)`` pseudo-event so callers handle both shapes."""
    status, headers, body = await http_request(host, port, "POST", path,
                                               payload)
    if "text/event-stream" not in headers.get("content-type", ""):
        obj = json.loads(body.decode("utf-8")) if body else {}
        return status, headers, [("error", obj)]
    return status, headers, parse_sse(body.decode("utf-8"))


async def scrape_metrics(host: str, port: int) -> str:
    _, _, body = await http_request(host, port, "GET", "/metrics")
    return body.decode("utf-8")


def _payloads(mode: str, n_requests: int, *, vocab_size: int, max_len: int,
              max_tokens: int, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_requests):
        n = int(rng.integers(4, max(max_len // 2, 6)))
        toks = rng.integers(1, vocab_size, size=n).tolist()
        if mode == "encode":
            out.append({"tokens": toks})
        else:
            out.append({"prompt": toks[:8], "max_tokens": max_tokens})
    return out


async def run_load(host: str, port: int, *, mode: str = "encode",
                   n_requests: int = 32, concurrency: int = 8,
                   vocab_size: int = 1000, max_len: int = 64,
                   max_tokens: int = 4, seed: int = 0) -> dict:
    """Fire ``n_requests`` at the front-end with at most ``concurrency``
    connections open; returns completion/rejection counts and the
    client-side latency summary (same buckets as the server histogram)."""
    payloads = _payloads(mode, n_requests, vocab_size=vocab_size,
                         max_len=max_len, max_tokens=max_tokens, seed=seed)
    sem = asyncio.Semaphore(concurrency)
    latencies: list[float] = []
    counts = {"completed": 0, "rejected": 0, "errors": 0, "tokens": 0}
    path = "/v1/encode" if mode == "encode" else "/v1/generate"

    async def one(payload):
        async with sem:
            t0 = time.perf_counter()
            if mode == "encode":
                status, _, obj = await http_json(host, port, "POST", path,
                                                 payload)
                ok = status == 200 and "logits" in obj
            else:
                status, _, events = await http_sse(host, port, path, payload)
                done = [d for e, d in events if e == "done"]
                ok = status == 200 and bool(done)
                if ok:
                    counts["tokens"] += len(done[0].get("tokens", []))
            if ok:
                counts["completed"] += 1
                latencies.append(time.perf_counter() - t0)
            elif status in (429, 503):
                counts["rejected"] += 1
            else:
                counts["errors"] += 1

    t0 = time.perf_counter()
    await asyncio.gather(*(one(p) for p in payloads))
    wall = time.perf_counter() - t0
    return {"mode": mode, "requests": n_requests,
            "concurrency": concurrency, "wall_s": wall,
            "requests_per_s": counts["completed"] / max(wall, 1e-9),
            **counts,
            "rejection_rate": counts["rejected"] / max(n_requests, 1),
            **latency_summary(latencies)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--mode", default="encode",
                    choices=("encode", "generate"))
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--vocab-size", type=int, default=1000)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-tokens", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="merge the result into this JSON file under "
                         "'http_load'")
    args = ap.parse_args()
    result = asyncio.run(run_load(
        args.host, args.port, mode=args.mode, n_requests=args.requests,
        concurrency=args.concurrency, vocab_size=args.vocab_size,
        max_len=args.max_len, max_tokens=args.max_tokens, seed=args.seed))
    print(f"[serve_http_load] {result['mode']}: {result['completed']} ok / "
          f"{result['rejected']} rejected / {result['errors']} errors in "
          f"{result['wall_s']:.2f}s ({result['requests_per_s']:.1f} req/s) "
          f"p50={result['p50_latency_s']:.3f}s "
          f"p99={result['p99_latency_s']:.3f}s "
          f"rejection_rate={result['rejection_rate']:.2f}")
    if args.out:
        blob = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                blob = json.load(f)
        blob.setdefault("http_load", {})[args.mode] = result
        with open(args.out, "w") as f:
            json.dump(blob, f, indent=1)
        print(f"[serve_http_load] merged into {args.out}")


if __name__ == "__main__":
    main()
