"""Plain float32 reference of one chip's expert-parallel share of
DeepSeek-V2-Lite: the full causal forward.

Straightforward ``jax.numpy`` at full float32 precision over a whole token
history at once: no kernels, no KV cache, no pages, no slots, no absorbed
projections. It imports nothing of the system under test; it reads the
benchmark's own float weights by their names in the parameter tree, takes
every width from the weights' shapes, and the published settings it
cannot read off a shape from the configuration file beside it.

It follows DeepSeek-V2 (arXiv:2405.04434; the ``deepseek-ai/DeepSeek-V2-Lite``
config and modeling code):

* RMSNorm (epsilon 1e-6) before attention and before the FFN, and a final
  one before the untied LM head;
* multi-head latent attention without query compression, expanded: the
  query projection gives each head 128 no-rope and 64 rope dims; the
  key/value projection gives a 512-wide latent, RMS-normed, and 64 rope
  dims shared by the heads; the latent expands to each head's 128 no-rope
  key dims and 128 value dims;
* YaRN rotary embedding on the rope dims (theta 1e4; factor 40 over 4096
  original positions, beta_fast 32, beta_slow 1): the inverse frequencies
  blend from the base ones to those over the factor along a linear ramp
  between the correction dims; cos and sin are scaled by mscale / mscale
  (1 here), and the softmax scale by the squared mscale of mscale_all_dim
  over the square root of the 192-wide query-key head;
* layer 0 a dense SwiGLU FFN, every other layer a mixture of experts: the
  router takes a softmax over all its experts (64) and keeps the greedy
  top 6 of those probabilities, unrenormalized, times the routed scaling
  factor; the experts this chip holds (16, from ``first_held_expert``)
  add their part, gate times SwiGLU expert, and the experts it does not
  hold add nothing; two shared experts (one SwiGLU of twice the expert
  width) add theirs for every token.

Departure from the published model: the rope dims are in the rotate-half
(split) layout, as the program computes them; the checkpoint stores them
interleaved, which under random weights is a fixed permutation of the rope
columns of the query and key/value projections.

``control`` puts the reference at the next precision below what the
configuration states: ``"int4"`` computes the GEMMs the SAMP ``ffn`` plan
runs in int8 (the dense FFN, the routed and the shared experts) with int4
weights (per output channel, per expert) and int4 activations (per
token), symmetric; ``"bf16"`` computes everything in bfloat16.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


@functools.cache
def _config() -> dict:
    """The configuration file beside this reference."""
    return json.loads(pathlib.Path(__file__).with_suffix(".json")
                      .read_text())


def _rms_norm(x, p):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + EPS) * p["scale"]


def _int4(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 7.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _mm(x, w, control=None):
    if control == "int4":
        x, w = _int4(x, -1), _int4(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _swiglu(x, p, control=None):
    h = jax.nn.silu(_mm(x, p["wg"]["w"], control)) \
        * _mm(x, p["wu"]["w"], control)
    return _mm(h, p["wd"]["w"], control)


def _yarn(rd: int):
    """(inverse frequencies (rd/2,), cos/sin scale, softmax-scale factor)."""
    rs = _config()["rope_scaling"]
    theta = float(_config()["rope_theta"])
    factor = float(rs["factor"])

    def corr(rotations):
        return (rd * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), rd - 1)
    extra = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ramp = jnp.clip((jnp.arange(rd // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0
    return (inv, mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]),
            mscale(rs["mscale_all_dim"]) ** 2)


def _rope(x, pos, inv, m):
    """Rotate-half rope on the last dim of x (S, ..., rd)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 2)
                                          + (1,)) * inv
    cos = (jnp.cos(ang) * m).astype(x.dtype)
    sin = (jnp.sin(ang) * m).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, a, heads, pos, causal):
    S = x.shape[0]
    r = a["kv_norm"]["scale"].shape[0]
    rd = a["wkv_a"]["w"].shape[1] - r
    qk = a["wq"]["w"].shape[1] // heads
    nope = qk - rd
    vd = a["wkv_b"]["w"].shape[1] // heads - nope
    inv, m, soft = _yarn(rd)
    q = _mm(x, a["wq"]["w"]).reshape(S, heads, qk)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, inv, m)
    kv = _mm(x, a["wkv_a"]["w"])
    ckv = _rms_norm(kv[:, :r], a["kv_norm"])
    k_pe = _rope(kv[:, r:], pos, inv, m)                        # (S, rd)
    kvb = _mm(ckv, a["wkv_b"]["w"]).reshape(S, heads, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (S, heads, rd))], -1)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("qhd,khd->hqk", qf, k, precision=HI) \
        * (soft / math.sqrt(qk))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    return _mm(o.reshape(S, heads * vd), a["wo"]["w"])


def _moe(x, f, control):
    """The held experts' part and the shared experts' for every token."""
    c = _config()
    K = c["num_experts_per_tok"]
    first = c["first_held_expert"]
    probs = jax.nn.softmax(_mm(x, f["router"]["w"]), -1)       # (S, 64)
    gates, idx = jax.lax.top_k(probs, K)
    if c["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    else:
        gates = gates * c["routed_scaling_factor"]
    held = f["wg"]["w"].shape[0]
    experts = first + jnp.arange(held)
    # each held expert's gate for each token: 0 where it was not picked
    weight = jnp.sum(jnp.where(idx[:, None, :] == experts[None, :, None],
                               gates[:, None, :], 0.0), -1)     # (S, held)
    xi = _int4(x, -1) if control == "int4" else x
    wg, wu, wd = (f[k]["w"] for k in ("wg", "wu", "wd"))
    if control == "int4":
        wg, wu, wd = _int4(wg, -2), _int4(wu, -2), _int4(wd, -2)
    h = jax.nn.silu(jnp.einsum("sd,edf->esf", xi, wg, precision=HI)) \
        * jnp.einsum("sd,edf->esf", xi, wu, precision=HI)
    if control == "int4":
        h = _int4(h, -1)
    y = jnp.einsum("esf,efd->esd", h, wd, precision=HI)
    routed = jnp.einsum("se,esd->sd", weight.astype(x.dtype), y,
                        precision=HI)
    return routed + _swiglu(x, f["shared"], control)


def logits_at(params, tokens, rows, *, heads: int, kv_heads: int,
              control=None):
    """(R, vocab) logits of the causal forward over ``tokens`` (S,), at the
    positions ``rows`` (R,). Positions after the real history only pad the
    end: causality keeps them out of every row that is read. MLA expands
    the one latent to a key and a value per query head, so ``kv_heads``
    is not read."""
    if control == "bf16":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
    S = tokens.shape[0]
    x = params["embed"]["tok"][tokens]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                        # (Sq, Sk)

    def block(x, lp):
        x = x + _attention(_rms_norm(x, lp["norm1"]), lp["attn"], heads,
                           pos, causal)
        h = _rms_norm(x, lp["norm2"])
        f = lp["ffn"]
        return x + (_moe(h, f, control) if "router" in f
                    else _swiglu(h, f, control)), None

    for group in params["groups"]:
        for stack in group["layers"]:
            x, _ = jax.lax.scan(block, x, stack)
    x = _rms_norm(x[rows], params["final_norm"])
    return _mm(x, params["lm_head"]["w"]).astype(jnp.float32)
