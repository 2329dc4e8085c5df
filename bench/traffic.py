"""The one traffic generator: a traffic file's parameters -> an open-loop
schedule.

A traffic file (``bench/traffic/<mix>.json``) holds numbers only:

* ``kind``: ``encoder`` (classification requests) or ``decode``
  (generation requests);
* ``arrivals``: ``{"process": "poisson", "rate": r}`` (requests per
  second);
* ``mix``: classes, each with its ``share`` of requests and its length
  ranges, drawn log-uniform over ``[lo, hi]``: ``tokens`` (encoder;
  ``"pair": true`` splits the request into two segments) or ``prompt`` and
  ``output`` (decode);
* ``base_seed``: fixes the set of sizes and of inter-arrival gaps;
* ``drain_s``: how long requests due in the window are followed after it.

Every seed gets the same set of sizes and arrivals, in another order: the
run's ``--seed`` permutes the request sizes and the gaps between arrivals,
and draws the token ids. So runs with different seeds
offer the same work, and the same seed gives the same inputs.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Item:
    """One scheduled request; ``due`` is seconds after the window opens."""
    uid: int
    due: float
    tokens: list                        # encoder input / decode prompt
    segments: Optional[list] = None     # encoder sentence pairs
    max_tokens: int = 0                 # decode output length


def arrival_times(arr: dict, seconds: float, base: np.random.Generator,
                  run: np.random.Generator) -> np.ndarray:
    """Due times inside ``[0, seconds)``: Poisson gaps from ``base``,
    permuted by ``run`` (their sum, and so the count in the window, is the
    same for every seed)."""
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate"])
    gaps = []
    t = 0.0
    while True:
        g = base.exponential(1.0 / rate)
        if t + g >= seconds:
            break
        t += g
        gaps.append(g)
    return np.cumsum(run.permutation(np.asarray(gaps, np.float64)))


def _log_uniform(rng: np.random.Generator, lo: int, hi: int, n: int):
    x = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), size=n))
    return np.clip(np.floor(x).astype(np.int64), lo, hi)


def _class_counts(shares: list, n: int) -> list:
    """Largest-remainder split of ``n`` requests by share."""
    raw = [s * n / sum(shares) for s in shares]
    counts = [int(math.floor(r)) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])[
            :n - sum(counts)]:
        counts[i] += 1
    return counts


def sizes(traffic: dict, n: int, base: np.random.Generator) -> list:
    """The fixed set of ``n`` request sizes, as dicts, in class order."""
    out = []
    mix = traffic["mix"]
    for cls, count in zip(mix, _class_counts([c["share"] for c in mix], n)):
        if traffic["kind"] == "encoder":
            lengths = _log_uniform(base, *cls["tokens"], count)
            splits = base.uniform(0.25, 0.75, size=count)
            for length, f in zip(lengths, splits):
                split = int(length * f) if cls.get("pair") else None
                out.append({"tokens": int(length), "split": split})
        else:
            prompts = _log_uniform(base, *cls["prompt"], count)
            outputs = _log_uniform(base, *cls["output"], count)
            out += [{"prompt": int(p), "output": int(o)}
                    for p, o in zip(prompts, outputs)]
    return out


def schedule(traffic: dict, seconds: float, seed: int,
             vocab_size: int) -> list:
    """The window's requests in due order."""
    base = np.random.default_rng(traffic["base_seed"])
    run = np.random.default_rng(seed)
    due = arrival_times(traffic["arrivals"], seconds, base, run)
    chosen = sizes(traffic, len(due), base)
    chosen = [chosen[i] for i in run.permutation(len(chosen))]
    items = []
    for uid, (t, s) in enumerate(zip(due, chosen)):
        if traffic["kind"] == "encoder":
            n = s["tokens"]
            toks = run.integers(1, vocab_size, size=n).tolist()
            segs = None
            if s["split"] is not None:
                segs = [0] * s["split"] + [1] * (n - s["split"])
            items.append(Item(uid, float(t), toks, segs))
        else:
            toks = run.integers(1, vocab_size, size=s["prompt"]).tolist()
            items.append(Item(uid, float(t), toks, max_tokens=s["output"]))
    return items


def longest(traffic: dict) -> int:
    """The most tokens one request of the mix can hold (decode: prompt plus
    output)."""
    if traffic["kind"] == "encoder":
        return max(c["tokens"][1] for c in traffic["mix"])
    return max(c["prompt"][1] + c["output"][1] for c in traffic["mix"])


def length_ranges(traffic: dict) -> list:
    """Every (lo, hi) input-length range of the mix."""
    key = "tokens" if traffic["kind"] == "encoder" else "prompt"
    return [tuple(c[key]) for c in traffic["mix"]]
