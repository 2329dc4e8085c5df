"""Plain float32 reference of bert-base with a CLS classification head.

Straightforward ``jax.numpy`` at full float32 precision: no kernels, no
cache, no buckets. It imports nothing of the system under test; it reads
the benchmark's own float weights by their names in the parameter tree.

It follows BERT (Devlin et al. 2019) with the departures the served model
makes, noted so that the comparison is of like with like:

* pre-LayerNorm blocks (``x + attn(LN(x))``, ``x + ffn(LN(x))``) and a
  final LayerNorm, where BERT is post-LN;
* LayerNorm epsilon 1e-6 (BERT: 1e-12);
* GELU in its tanh form (BERT: erf);
* the pooler is ``tanh(W h[CLS] + b)`` as in BERT, then one linear layer
  to the classes.

``control`` puts the reference at the next precision below what the
configuration states: ``"int4"`` computes every block GEMM with int4
weights (per output channel) and int4 activations (per token), symmetric,
below the int8 of the SAMP plan; ``"bf16"`` computes everything in
bfloat16 (weights, activations, norms, softmax and logits), below the
float32 of the parts the plan keeps float.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def _layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _int4(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _linear(x, p, control):
    w = p["w"]
    if control == "int4":
        x, w = _int4(x, -1), _int4(w, 0)
    y = jnp.matmul(x, w, precision=HI)
    return y + p["b"] if "b" in p else y


def _stack_layers(params):
    groups = [g["layers"][0] for g in params["groups"]]
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *groups)


def logits(params, tokens, segments, lengths, *, heads: int,
           kv_heads: int, control=None):
    """(B, classes) logits for ``tokens``/``segments`` (B, S), of which the
    first ``lengths`` (B,) positions are real. ``kv_heads`` below ``heads``
    shares each key/value head among a group of query heads (bert-base
    has as many of each)."""
    if control == "bf16":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
    emb = params["embed"]
    B, S = tokens.shape
    x = emb["tok"][tokens] + emb["pos"][jnp.arange(S)][None] \
        + emb["seg"][segments]
    x = _layer_norm(x, emb["emb_norm"])
    keep = jnp.arange(S)[None, :] < lengths[:, None]            # (B, S)
    D = x.shape[-1]
    hd = D // heads
    g = heads // kv_heads

    def block(x, lp):
        h = _layer_norm(x, lp["norm1"])
        a = lp["attn"]
        q = _linear(h, a["wq"], control).reshape(B, S, kv_heads, g, hd)
        k, v = (_linear(h, a[n], control).reshape(B, S, kv_heads, hd)
                for n in ("wk", "wv"))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                       precision=HI) / math.sqrt(hd)
        s = jnp.where(keep[:, None, None, None, :], s, -jnp.inf)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v,
                       precision=HI).reshape(B, S, D)
        x = x + _linear(o, a["wo"], control)
        h = _layer_norm(x, lp["norm2"])
        f = lp["ffn"]
        h = jax.nn.gelu(_linear(h, f["wi"], control), approximate=True)
        return x + _linear(h, f["wo"], control), None

    x, _ = jax.lax.scan(block, x, _stack_layers(params))
    x = _layer_norm(x, params["final_norm"])
    head = params["head"]
    pooled = jnp.tanh(jnp.matmul(x[:, 0], head["pool"]["w"], precision=HI)
                      + head["pool"]["b"])
    out = jnp.matmul(pooled, head["out"]["w"], precision=HI) \
        + head["out"]["b"]
    return out.astype(jnp.float32)
