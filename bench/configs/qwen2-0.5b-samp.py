"""Plain float32 reference of qwen2-0.5b: the full causal forward.

Straightforward ``jax.numpy`` at full float32 precision over a whole token
history at once: no kernels, no KV cache, no pages, no slots. It imports
nothing of the system under test; it reads the benchmark's own float
weights by their names in the parameter tree.

It follows Qwen2 (arXiv:2407.10671; the ``Qwen/Qwen2-0.5B`` config):
RMSNorm (epsilon 1e-6), q/k/v projections with bias and o without, grouped
query attention (14 query heads over 2 key/value heads), rotary embedding
in the split-half (``rotate_half``) form with theta 1e6, SwiGLU feed
forward, a final RMSNorm, and the LM head tied to the token embedding.

``control`` puts the reference at the next precision below what the
configuration states: ``"int4"`` computes the feed-forward GEMMs with int4
weights (per output channel) and int4 activations (per token), symmetric,
below the int8 that the SAMP ``ffn`` plan states for them; ``"bf16"``
computes everything in bfloat16 (the attention projections, the tied LM
head, norms, softmax and logits), below the float32 of the parts the plan
keeps float.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6
ROPE_THETA = 1_000_000.0


def _rms_norm(x, p):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + EPS) * p["scale"]


def _int4(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _linear(x, p, control=None):
    w = p["w"]
    if control == "int4":
        x, w = _int4(x, -1), _int4(w, 0)
    y = jnp.matmul(x, w, precision=HI)
    return y + p["b"] if "b" in p else y


def _rope(x, pos):
    half = x.shape[-1] // 2
    inv = 1.0 / (ROPE_THETA ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None, None] * inv          # (S, 1, hd/2)
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _stack_layers(params):
    groups = [g["layers"][0] for g in params["groups"]]
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *groups)


def logits_at(params, tokens, rows, *, heads: int, kv_heads: int,
              control=None):
    """(R, vocab) logits of the causal forward over ``tokens`` (S,), at the
    positions ``rows`` (R,). Positions after the real history only pad the
    end: causality keeps them out of every row that is read."""
    if control == "bf16":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
    S = tokens.shape[0]
    tok = params["embed"]["tok"]
    x = tok[tokens]
    D = x.shape[-1]
    hd = D // heads
    g = heads // kv_heads
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                        # (Sq, Sk)

    def block(x, lp):
        h = _rms_norm(x, lp["norm1"])
        a = lp["attn"]
        q = _rope(_linear(h, a["wq"]).reshape(S, heads, hd), pos)
        k = _rope(_linear(h, a["wk"]).reshape(S, kv_heads, hd), pos)
        v = _linear(h, a["wv"]).reshape(S, kv_heads, hd)
        q = q.reshape(S, kv_heads, g, hd)
        s = jnp.einsum("qhgd,khd->hgqk", q, k, precision=HI) / math.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v,
                       precision=HI).reshape(S, D)
        x = x + _linear(o, a["wo"])
        h = _rms_norm(x, lp["norm2"])
        f = lp["ffn"]
        h = jax.nn.silu(_linear(h, f["wg"], control)) \
            * _linear(h, f["wu"], control)
        return x + _linear(h, f["wd"], control), None

    x, _ = jax.lax.scan(block, x, _stack_layers(params))
    x = _rms_norm(x[rows], params["final_norm"])
    return jnp.matmul(x, tok.T, precision=HI).astype(jnp.float32)
