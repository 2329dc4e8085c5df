"""CI smoke for the HTTP/SSE serving front-end.

    PYTHONPATH=src python tools/http_smoke.py [--arch qwen2-0.5b] \
        [--plan tests/data/golden_plan.json]

Boots ``launch/server.py`` as a subprocess on an ephemeral port (the
deployment CI actually ships: golden plan, encoder task on a
decode-capable arch, so BOTH endpoints are mounted), then walks the full
contract surface:

1. ``POST /v1/encode``    -> 200 with ``logits`` + ``prediction``;
2. ``POST /v1/generate``  -> SSE ``token`` events then one ``done``;
3. ``GET /metrics``       -> 200 with every name in
   ``repro.serve.metrics.CORE_METRICS``;
4. traffic-class routing  -> an encode tagged ``X-SAMP-Traffic-Class``
   lands in that cluster's ``samp_cluster_requests_total`` counter
   (the server boots with ``--clusters task:chat,search`` by default;
   pass ``--clusters ''`` for an unrouted smoke);
5. ``GET /healthz``       -> 200;
6. ``SIGTERM``            -> graceful drain, exit code 0.

Exits non-zero on any violation — this is the gate that keeps
docs/http-serving.md truthful.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.serve.frontend.protocol import parse_sse  # noqa: E402
from repro.serve.metrics import CORE_METRICS  # noqa: E402


def fail(msg: str) -> None:
    print(f"[http_smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def request(port: int, method: str, path: str, payload=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    body = None if payload is None else json.dumps(payload)
    conn.request(method, path, body=body, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    headers = {k.lower(): v for k, v in resp.getheaders()}
    conn.close()
    return resp.status, headers, data


def boot(args) -> tuple[subprocess.Popen, int]:
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    cmd = [sys.executable, "-m", "repro.launch.server",
           "--arch", args.arch, "--reduced", "--task", args.task,
           "--port", "0", "--slots", "2", "--max-len", "64"]
    if args.plan:
        cmd += ["--plan", args.plan]
    else:
        cmd += ["--policy", args.policy]
    if args.clusters:
        cmd += ["--clusters", args.clusters]
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)
    deadline = time.monotonic() + args.boot_timeout
    for line in proc.stdout:
        print(f"  [server] {line.rstrip()}")
        m = re.search(r"listening on http://[\d.]+:(\d+)", line)
        if m:
            return proc, int(m.group(1))
        if time.monotonic() > deadline:
            break
    proc.kill()
    fail("server never reported its listening port")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b",
                    help="decode-capable arch so both endpoints mount")
    ap.add_argument("--task", default="tnews")
    ap.add_argument("--plan", default="tests/data/golden_plan.json")
    ap.add_argument("--policy", default="ffn")
    ap.add_argument("--clusters", default="task:chat,search",
                    help="--clusters spec for the server ('' = unrouted)")
    ap.add_argument("--boot-timeout", type=float, default=300.0)
    args = ap.parse_args()

    proc, port = boot(args)
    try:
        status, _, body = request(port, "POST", "/v1/encode",
                                  {"tokens": [2, 17, 9, 41, 7]})
        if status != 200:
            fail(f"/v1/encode -> {status}: {body[:200]!r}")
        obj = json.loads(body)
        if not obj.get("logits") or "prediction" not in obj:
            fail(f"/v1/encode body missing logits/prediction: {obj}")
        print(f"[http_smoke] encode ok: prediction={obj['prediction']} "
              f"({len(obj['logits'])} logits, {obj['latency_ms']:.1f} ms)")

        status, headers, body = request(port, "POST", "/v1/generate",
                                        {"prompt": [2, 17, 9],
                                         "max_tokens": 4})
        if status != 200 or "text/event-stream" not in headers.get(
                "content-type", ""):
            fail(f"/v1/generate -> {status} "
                 f"{headers.get('content-type')}: {body[:200]!r}")
        events = parse_sse(body.decode("utf-8"))
        tokens = [d["token"] for e, d in events if e == "token"]
        done = [d for e, d in events if e == "done"]
        if not tokens or not done or done[0]["tokens"] != tokens:
            fail(f"/v1/generate stream malformed: {events}")
        print(f"[http_smoke] generate ok: {len(tokens)} tokens streamed, "
              f"finish_reason={done[0]['finish_reason']}")

        status, _, body = request(port, "GET", "/metrics")
        if status != 200:
            fail(f"/metrics -> {status}")
        text = body.decode("utf-8")
        missing = [n for n in CORE_METRICS if n not in text]
        if missing:
            fail(f"/metrics missing: {missing}")
        print(f"[http_smoke] metrics ok: all {len(CORE_METRICS)} core "
              f"names present ({len(text.splitlines())} lines)")

        # KV-cache gauges: a decode engine is mounted, so the cache
        # footprint must be a real (positive) byte count, and the page
        # gauge must expose a numeric sample (0 once requests retire)
        def gauge_value(name):
            m = re.search(rf"^{name} ([0-9.e+-]+)$", text, re.M)
            if not m:
                fail(f"/metrics has no plain sample for {name}")
            return float(m.group(1))

        kv_bytes = gauge_value("samp_kv_cache_bytes")
        kv_pages = gauge_value("samp_kv_pages_in_use")
        if kv_bytes <= 0:
            fail(f"samp_kv_cache_bytes = {kv_bytes}, want > 0 with a "
                 f"decode engine mounted")
        if kv_pages < 0:
            fail(f"samp_kv_pages_in_use = {kv_pages}, want >= 0")
        print(f"[http_smoke] kv gauges ok: samp_kv_cache_bytes={kv_bytes:g} "
              f"samp_kv_pages_in_use={kv_pages:g}")

        # traffic-class routing: a tagged encode must land in that
        # cluster's admission counter — the header round-trips through
        # protocol parsing, router admission, and the metrics exporter
        if args.clusters:
            def cluster_count(text, cluster):
                m = re.search(r'^samp_cluster_requests_total\{[^}]*'
                              rf'cluster="{cluster}"[^}}]*\}} ([0-9.e+-]+)$',
                              text, re.M)
                return float(m.group(1)) if m else None

            # task:chat,search -> "search" is cluster id 1
            before = cluster_count(text, 1) or 0.0
            status, _, body = request(
                port, "POST", "/v1/encode", {"tokens": [2, 17, 9]},
                headers={"X-SAMP-Traffic-Class": "search"})
            if status != 200:
                fail(f"tagged /v1/encode -> {status}: {body[:200]!r}")
            status, _, body = request(port, "GET", "/metrics")
            text = body.decode("utf-8")
            after = cluster_count(text, 1)
            if after is None or after != before + 1:
                fail(f"X-SAMP-Traffic-Class did not round-trip: "
                     f"cluster 1 count {before} -> {after}")
            m = re.search(r"^samp_active_plans\{[^}]*\} ([0-9.e+-]+)$",
                          text, re.M)
            if not m or float(m.group(1)) < 1:
                fail(f"samp_active_plans missing/zero on a routed "
                     f"deployment")
            print(f"[http_smoke] routing ok: tagged request counted "
                  f"(cluster 1: {before:g} -> {after:g}, "
                  f"active_plans={float(m.group(1)):g})")

        status, _, _ = request(port, "GET", "/healthz")
        if status != 200:
            fail(f"/healthz -> {status}")

        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        if rc != 0:
            fail(f"SIGTERM drain exited {rc}")
        print("[http_smoke] graceful drain ok (exit 0)")
        print("[http_smoke] PASS")
    finally:
        if proc.poll() is None:
            proc.kill()


if __name__ == "__main__":
    main()
