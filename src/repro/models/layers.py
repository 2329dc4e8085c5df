"""Quantization-aware building blocks shared by every architecture family.

Every GEMM in the model zoo goes through :func:`dense` (projections) or the
quant-aware batched matmuls inside :func:`attention_core`, so the SAMP
precision lattice (repro.core.precision) applies uniformly: a layer's
parameters either hold float weights (``jnp.ndarray``) or
:class:`~repro.core.quantize.QuantizedTensor` weights plus static activation
scales, and dispatch is structural (pytree leaf type), not flag-driven.

Conventions
-----------
* params are plain nested dicts of arrays; a "linear" is
  ``{"w": array|QuantizedTensor, ["b": array], ["xs": scalar]}`` where ``xs``
  is the calibrated per-tensor activation scale (absent => float GEMM, or
  dynamic per-token quantization when ``xs`` is absent but w is quantized).
* every function takes/returns activations in ``cfg``'s compute dtype.
* observer capture: functions append per-site ``amax`` scalars into an
  ``obs`` dict when one is passed (calibration mode); ``obs=None`` is the
  production path and adds no ops.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.quantize import (QuantizedTensor, compute_scale_symmetric,
                                 dequantize, int8_matmul, quantize,
                                 quantize_per_token, quantize_unsigned,
                                 INT8_MAX, UINT8_MAX)
from repro.kernels.backend import ACTIVATIONS as _ACT
from repro.kernels.backend import QuantActivation

# ---------------------------------------------------------------------------
# observer plumbing
# ---------------------------------------------------------------------------


def observe(obs: Optional[dict], site: str, x) -> None:
    """Record max|x| for a quantization site (calibration mode only).
    Pre-quantized activations are never observed — capture runs on the
    float model with the reference backend."""
    if obs is not None and not isinstance(x, QuantActivation):
        obs[site] = jnp.max(jnp.abs(x)).astype(jnp.float32)


def observe_values(obs: Optional[dict], site: str, x) -> None:
    """Record raw values for histogram calibrators (small models only)."""
    if obs is not None and obs.get("__values__", False) \
            and not isinstance(x, QuantActivation):
        obs.setdefault("__raw__", {})[site] = x


def observe_per_head(obs: Optional[dict], site: str, x) -> None:
    """Record per-head max|x| over (B, S, H, d) — the KV-cache calibration
    sites (``k_cache``/``v_cache``), whose static scales are per-head."""
    if obs is not None and not isinstance(x, QuantActivation):
        obs[site] = jnp.max(jnp.abs(x), axis=(0, 1, 3)).astype(jnp.float32)


def observe_per_expert(obs: Optional[dict], site: str, x) -> None:
    """Record per-expert max|x| over a routed (..., E, C, D) capacity
    buffer — the ``expert_in``/``expert_hidden`` calibration sites of the
    schema-v4 ``experts`` family, whose static scales are per-expert (E,).
    Aggregation over the capacity axis is exact: each expert's amax covers
    precisely the tokens routed to it (dropped tokens scatter as zeros,
    which never raise a max of real activations)."""
    if obs is not None and not isinstance(x, QuantActivation):
        e_axis = x.ndim - 3
        axes = tuple(i for i in range(x.ndim) if i != e_axis)
        obs[site] = jnp.max(jnp.abs(x), axis=axes).astype(jnp.float32)


# ---------------------------------------------------------------------------
# quant-aware GEMMs
# ---------------------------------------------------------------------------


def _act_quantize(x: jax.Array, xs: Optional[jax.Array]) -> QuantizedTensor:
    """Quantize activations: static per-tensor scale when calibrated
    (paper-faithful), per-token dynamic otherwise (beyond-paper)."""
    if xs is not None:
        return QuantizedTensor(quantize(x, xs), xs, None)
    return quantize_per_token(x)


def dense(x, p: dict, obs: Optional[dict] = None,
          site: str = "x", backend=None,
          act: Optional[str] = None) -> jax.Array:
    """y = act(x @ w (+ b)). Dispatches on the weight leaf type:

    * ``jnp.ndarray`` — float GEMM in x.dtype
    * ``QuantizedTensor`` — W8A8 int8 GEMM with int32 accumulation

    ``backend`` (a :mod:`repro.kernels.backend` ComputeBackend) may claim
    the op — the fused backend routes int8 blocks through the Pallas
    ``quant_linear`` kernel — or decline (None), keeping this reference
    path. ``x`` may arrive pre-quantized (a
    :class:`~repro.kernels.backend.QuantActivation` from the fused addnorm
    kernel); the reference path dequantizes it back.
    """
    observe(obs, site, x)
    observe_values(obs, site, x)
    if backend is not None:
        y = backend.linear(x, p, act=act)
        if y is not None:
            return y
    if isinstance(x, QuantActivation):
        x = x.dequantize()
    w = p["w"]
    if isinstance(w, QuantizedTensor):
        xq = _act_quantize(x, p.get("xs"))
        y = int8_matmul(xq, w, out_dtype=x.dtype)
    else:
        y = jax.lax.dot_general(
            x, w.astype(x.dtype),
            dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    y = _ACT[act](y) if act is not None else y
    if "out_xs" in p:
        # norm='int8' span: the fused kernel requantizes this GEMM's output
        # in its epilogue; the reference path mirrors that as a QDQ at the
        # same calibrated scale so backend choice never changes numerics.
        oxs = p["out_xs"]
        y = QuantizedTensor(quantize(y, oxs), oxs, None).dequantize(y.dtype)
    return y


def quant_bmm(a: jax.Array, b: jax.Array,
              a_scale: Optional[jax.Array], b_scale: Optional[jax.Array],
              *, transpose_b: bool = False,
              unsigned_a: bool = False) -> jax.Array:
    """Quantized batched matmul for the MHA score/value paths.

    ``a``/``b`` are float activations; both get quantized with the provided
    static scales (or dynamically when None), multiplied in int8 with int32
    accumulation, and dequantized. ``unsigned_a`` uses the asymmetric
    unsigned-range scheme for ``a`` (beyond-paper softmax fix).
    Contracts the last dim of ``a`` with the last (transpose_b) or
    second-to-last dim of ``b``; leading dims are batch.
    """
    if unsigned_a:
        aq = quantize_unsigned(a, None if a_scale is None else a_scale * UINT8_MAX)
    else:
        if a_scale is None:
            aq = quantize_per_token(a)
        else:
            aq = QuantizedTensor(quantize(a, a_scale), a_scale, None)
    if b_scale is None:
        bq_vals = quantize(b, compute_scale_symmetric(jnp.max(jnp.abs(b))))
        b_scale = compute_scale_symmetric(jnp.max(jnp.abs(b)))
    else:
        bq_vals = quantize(b, b_scale)
    bdim = b.ndim - 1 if transpose_b else b.ndim - 2
    nbatch = a.ndim - 2
    dn = (((a.ndim - 1,), (bdim,)),
          (tuple(range(nbatch)), tuple(range(nbatch))))
    acc = jax.lax.dot_general(aq.values, bq_vals, dimension_numbers=dn,
                              preferred_element_type=jnp.int32)
    if unsigned_a:
        # zero-point correction: sum over the contracted axis of b.
        bsum = jnp.sum(bq_vals.astype(jnp.int32), axis=bdim)
        if not transpose_b:
            acc = acc - aq.zero_point * bsum[..., None, :]
        else:
            acc = acc - aq.zero_point * bsum[..., None, :]
    return (acc.astype(jnp.float32) * (aq.scale * b_scale)).astype(a.dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, p: dict, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x: jax.Array, p: dict, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm(x: jax.Array, p: dict, kind: str, eps: float = 1e-6) -> jax.Array:
    return layer_norm(x, p, eps) if kind == "layernorm" else rms_norm(x, p, eps)


def residual_norm(delta: jax.Array, x: jax.Array, p: dict, kind: str, *,
                  next_scale=None, backend=None,
                  constrain=lambda t, _tag: t):
    """The residual boundary: ``(x + delta, norm(x + delta))``.

    When a fused backend claims it and ``next_scale`` carries the consuming
    GEMM's static activation scale, the Pallas ``addnorm_quant`` kernel
    computes both outputs in one pass and returns the norm output
    **pre-quantized** (a QuantActivation) — the paper's int8 inter-kernel
    dataflow. Otherwise: reference add + norm.
    """
    if backend is not None and next_scale is not None:
        fused = backend.addnorm(delta, x, p, kind, next_scale)
        if fused is not None:
            x_new, h = fused
            return constrain(x_new, "residual"), h
    if isinstance(delta, QuantActivation):
        delta = delta.dequantize()      # int8 span ends here (no fused claim)
    x_new = constrain(x + delta, "residual")
    return x_new, norm(x_new, p, kind)


def init_norm(kind: str, dim: int, dtype=jnp.float32) -> dict:
    p = {"scale": jnp.ones((dim,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the even half of the head dim (f32)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               heads_axis: bool = True) -> jax.Array:
    """x: (..., S, H, hd) when ``heads_axis`` else (..., S, hd);
    positions: (S,) int32 (uniform across batch — prefill/train) or (B, S)
    (per-row — continuous-batching decode). Split-half convention."""
    head_dim = x.shape[-1]
    inv = rope_frequencies(head_dim, theta)                  # (hd/2,)
    ang = positions.astype(jnp.float32)[..., :, None] * inv  # (..., S, hd/2)
    if heads_axis:
        ang = ang[..., :, None, :]                           # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


@dataclasses.dataclass(frozen=True)
class AttnQuant:
    """Static quant plan for one attention block's batched matmuls.

    ``softmax_mode``: 'symmetric' reproduces the paper's pathology
    (Appendix B), 'unsigned' is the beyond-paper fix, 'none' keeps the
    softmax output float even when the rest of MHA is quantized.

    ``plan_scheme`` is the layer's schema-v3 ``softmax`` scheme ('uint8' or
    None) from the PrecisionPlan — per-layer, overriding the global
    ``softmax_mode`` policy: 'uint8' forces the unsigned quantized-softmax
    dataflow in the quant-MHA path, and makes the *reference* (float-bmm /
    decode-gather) paths quantize-dequantize the softmax output at the
    calibrated ``p`` scale so backend choice never changes numerics.
    """
    enabled: bool = False
    softmax_mode: str = "symmetric"
    plan_scheme: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Attention-visibility rule, evaluated lazily per query block so the
    full (Sq, Sk) mask never materializes at 32k+ sequence lengths."""
    causal: bool = True
    window: Optional[int] = None         # sliding-window width (None = full)
    prefix_len: int = 0                  # bidirectional prefix (prefix-LM)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return (jnp.tanh(x.astype(jnp.float32) / cap) * cap).astype(x.dtype)


def band_mask(q_pos: jax.Array, k_pos: jax.Array, spec: MaskSpec) -> jax.Array:
    """Boolean (..., Sq, Sk) mask: True = attend. ``q_pos``/``k_pos`` are
    int32 position ids of shape (Sq,)/(Sk,) or (B, Sq)/(B, Sk); invalid
    cache slots carry position -1 (masked by the causal >= 0 check)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    valid = kp >= 0
    if spec.causal:
        m = kp <= qp
        if spec.prefix_len:
            m = m | (kp < spec.prefix_len)
    else:
        m = jnp.broadcast_to(jnp.asarray(True), jnp.broadcast_shapes(
            qp.shape, kp.shape))
    if spec.window is not None:
        m = m & (kp > qp - spec.window)
    return m & valid


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, k_pos: jax.Array, spec: MaskSpec, *,
                   scale: float,
                   attn_softcap: Optional[float] = None,
                   quant: AttnQuant = AttnQuant(),
                   scales: Optional[dict] = None,
                   obs: Optional[dict] = None,
                   constrain=lambda t, _tag: t,
                   chunk: Optional[int] = None) -> jax.Array:
    """softmax(q k^T / sqrt(d)) v with GQA head-group broadcast and optional
    int8 score/value matmuls (SAMP Fully-Quant MHA path).

    q: (B, Sq, Hq, d)   k,v: (B, Sk, Hkv, d);  positions per MaskSpec.
    ``chunk``: process queries in blocks of this many rows via lax.scan so
    the (Sq, Sk) score matrix never materializes for the full sequence
    (memory-efficient attention; the Pallas flash kernel is the TPU
    hot-path, this is the composable XLA fallback).
    """
    B, Sq, Hq, D = q.shape
    Dv = v.shape[-1]                                # may differ (MLA)
    Hkv = k.shape[2]
    groups = Hq // Hkv
    qh = q.transpose(0, 2, 1, 3)                    # (B, Hq, Sq, d)
    kh = k.transpose(0, 2, 1, 3)                    # (B, Hkv, Sk, d)
    vh = v.transpose(0, 2, 1, 3)
    if groups > 1 and quant.enabled:
        # int8 batched matmuls need matching batch ranks; GQA encoders in
        # the paper's scope are MHA, so the repeat here is small
        kh = jnp.repeat(kh, groups, axis=1)
        vh = jnp.repeat(vh, groups, axis=1)
    grouped = groups > 1 and not quant.enabled
    sc = scales or {}
    if q_pos.ndim == 1:
        q_pos = q_pos[None]                         # (1, Sq)
    if k_pos.ndim == 1:
        k_pos = k_pos[None]

    def block(qb: jax.Array, qp: jax.Array) -> jax.Array:
        # qb: (B, Hq, bq, d); qp: (B|1, bq)
        mb = band_mask(qp, k_pos, spec)             # (B|1, bq, Sk)
        qs = qb * scale
        observe(obs, "q", qs)                       # bmm operands observed in
        observe(obs, "k", kh)                       # float calibration too
        if quant.enabled:
            s = quant_bmm(qs, kh, sc.get("q"), sc.get("k"), transpose_b=True)
        elif grouped:
            # GQA without materializing repeated K/V: fold the query-head
            # groups into an extra einsum axis (16x less K/V HBM traffic
            # for MQA archs, and no SPMD resharding of repeated tensors)
            bq = qs.shape[2]
            qg = qs.reshape(B, Hkv, groups, bq, D)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kh)
            s = s.reshape(B, Hq, bq, -1)
        else:
            s = jnp.einsum("bhqd,bhkd->bhqk", qs, kh)
        s = softcap(s, attn_softcap)
        s = jnp.where(mb[:, None], s.astype(jnp.float32), NEG_INF)
        # pin the score layout: without this, GSPMD may pick a different
        # (head-split) sharding for the softmax BACKWARD and pay full
        # score-tensor reshards each direction
        s = constrain(s, "attn_scores")
        p = constrain(jax.nn.softmax(s, axis=-1).astype(qb.dtype),
                      "attn_scores")
        observe(obs, "p", p)
        observe_values(obs, "p", p)
        observe(obs, "v", vh)
        if (not quant.enabled and quant.plan_scheme == "uint8"
                and sc.get("p") is not None):
            # plan says softmax='uint8' but this path keeps float bmms
            # (e.g. the reference decode gather): QDQ the probabilities at
            # the calibrated scale so numerics match the fused kernels,
            # which quantize p in their PV epilogue.
            p = quantize_unsigned(p, sc["p"] * UINT8_MAX).dequantize(p.dtype)
        if quant.enabled and (quant.softmax_mode != "none"
                              or quant.plan_scheme == "uint8"):
            p_scale = sc.get("p")
            o = quant_bmm(p, vh, p_scale, sc.get("v"),
                          unsigned_a=(quant.softmax_mode == "unsigned"
                                      or quant.plan_scheme == "uint8"))
        elif grouped:
            bq = p.shape[2]
            pg = p.reshape(B, Hkv, groups, bq, -1)
            o = jnp.einsum("bhgqk,bhkd->bhgqd", pg, vh)
            o = o.reshape(B, Hq, bq, Dv)
        else:
            o = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
        return o

    if chunk is not None and Sq % chunk != 0:
        # round down to the largest divisor of Sq (prefix-LM lengths etc.)
        c = chunk
        while c > 1 and Sq % c:
            c -= 1
        chunk = c if c > 1 else None
    if chunk is None or Sq <= chunk:
        out = block(qh, q_pos)
    else:
        nb = Sq // chunk
        qb = qh.reshape(B, Hq, nb, chunk, D).transpose(2, 0, 1, 3, 4)
        pb = q_pos.reshape(q_pos.shape[0], nb, chunk).transpose(1, 0, 2)

        def body(_, qm):
            qi, pi = qm
            return None, jax.checkpoint(block)(qi, pi)

        _, ob = jax.lax.scan(body, None, (qb, pb))
        out = ob.transpose(1, 2, 0, 3, 4).reshape(B, Hq, Sq, Dv)
    return out.transpose(0, 2, 1, 3)                # (B, Sq, Hq, d)


# ---------------------------------------------------------------------------
# GQA attention block (projections + core); also MQA/full/sliding/softcap
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, bias: bool = False,
                dtype=jnp.float32, init_scale: float = 1.0) -> dict:
    std = init_scale / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * std}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def init_attention(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": init_linear(ks[0], cfg.d_model, cfg.q_dim, cfg.qkv_bias, dtype),
        "wk": init_linear(ks[1], cfg.d_model, cfg.kv_dim, cfg.qkv_bias, dtype),
        "wv": init_linear(ks[2], cfg.d_model, cfg.kv_dim, cfg.qkv_bias, dtype),
        "wo": init_linear(ks[3], cfg.q_dim, cfg.d_model, False, dtype),
    }


def _cache_write(kv_cache: dict, new: dict, positions: jax.Array,
                 active: Optional[jax.Array]):
    """Write new K/V(-like) tensors into a ring-buffer cache.

    Two modes:
    * uniform positions (``positions`` 1-D, prefill / synchronized decode):
      contiguous dynamic_update_slice at slot pos%W for every row;
    * per-row positions (``positions`` (B, 1), continuous-batching decode):
      scatter one token per row at that row's own slot; rows with
      ``active=False`` rewrite their old value (a no-op), so idle slots in a
      serving batch are never corrupted.

    ``new`` maps cache key -> (B, S, ...) tensor. Returns the updated cache
    (with "k_pos"/"pos" bookkeeping).
    """
    W = kv_cache["k_pos"].shape[-1]
    B = kv_cache["k_pos"].shape[0]
    out = dict(kv_cache)
    if positions.ndim == 1:                          # uniform path
        S = positions.shape[0]
        write_S = min(S, W)      # ring smaller than prefill: keep the tail
        slot = kv_cache["pos"][0] % W
        if write_S < S:
            slot = slot * 0      # tail fills the whole ring from slot 0
        for key, val in new.items():
            val = val[:, S - write_S:]
            out[key] = jax.lax.dynamic_update_slice(
                kv_cache[key], val.astype(kv_cache[key].dtype),
                (0, slot) + (0,) * (val.ndim - 2))
        kp = jnp.broadcast_to(
            positions.astype(jnp.int32)[None, S - write_S:], (B, write_S))
        out["k_pos"] = jax.lax.dynamic_update_slice(
            kv_cache["k_pos"], kp, (0, slot))
        out["pos"] = kv_cache["pos"] + S
    else:                                            # per-row path (S == 1)
        rows = jnp.arange(B)
        pos_vec = positions[:, 0]
        slot = pos_vec % W
        act = active if active is not None else jnp.ones((B,), bool)
        for key, val in new.items():
            old_row = kv_cache[key][rows, slot]      # (B, ...)
            val_row = val[:, 0].astype(kv_cache[key].dtype)
            val_row = jnp.where(
                act.reshape((B,) + (1,) * (val_row.ndim - 1)),
                val_row, old_row)
            out[key] = kv_cache[key].at[rows, slot].set(val_row)
        old_kp = kv_cache["k_pos"][rows, slot]
        out["k_pos"] = kv_cache["k_pos"].at[rows, slot].set(
            jnp.where(act, pos_vec.astype(jnp.int32), old_kp))
        out["pos"] = kv_cache["pos"] + act.astype(kv_cache["pos"].dtype)
    return out


# ---------------------------------------------------------------------------
# paged KV cache (decode serving)
# ---------------------------------------------------------------------------
#
# Layout: the per-slot (B, W, ...) ring of `_cache_write` becomes a pooled
# set of fixed-size token pages shared by every slot:
#
#   pages_k / pages_v : (NP, Hkv, ps, hd)   int8 or cache dtype
#   pages_ks/pages_vs : (NP, Hkv, ps) f32   per-token scales (dynamic only)
#   pages_pos         : (NP, ps) int32      absolute position, -1 = invalid
#   pos               : (B,) int32          per-slot next position
#
# plus a page-table *operand* (B, pages_per_slot) int32 owned by the
# serving scheduler's PagePool (-1 = unallocated). Token t of slot b lives
# at page pages[b, t // ps], row t % ps. K/V pages are head-major so the
# Pallas decode kernel reads one head's (ps, hd) page as one whole TPU
# block. Slots stop paying max-length memory: pages are allocated as
# generation grows and returned to the pool on completion/cancel. MLA
# caches page the latent instead (pages_ckv / pages_krope).


#: page leaves stored head-major, (NP, Hkv, ps, ...); the latent (MLA)
#: pages have no head axis and stay token-major, (NP, ps, ...)
HEAD_MAJOR_PAGES = ("k", "v")
#: the kv-head axis of a head-major page leaf
PAGE_HEAD_AXIS = 1


def page_leaf_shape(num_pages: int, page_size: int, heads: int,
                    *tail: int) -> tuple:
    """Allocation shape of a head-major page leaf: K/V pages with
    ``tail=(head_dim,)``, their per-token scales with no tail."""
    return (num_pages, heads, page_size) + tail


def _page_flat_index(pages: jax.Array, positions: jax.Array,
                     active: Optional[jax.Array],
                     page_size: int) -> jax.Array:
    """(B, S) flat token indices into a (NP*ps, ...) page pool; -1 where the
    write must be dropped (inactive row, unallocated page, out of range)."""
    pidx = positions // page_size                       # (B, S)
    within = positions % page_size
    pps = pages.shape[1]
    safe = jnp.clip(pidx, 0, pps - 1)
    pt = jnp.take_along_axis(pages.astype(jnp.int32), safe, axis=1)
    ok = (pt >= 0) & (pidx >= 0) & (pidx < pps)
    if active is not None:
        ok = ok & active[:, None]
    return jnp.where(ok, pt * page_size + within, -1)


def _paged_cache_write(kv_cache: dict, new: dict, positions: jax.Array,
                       active: Optional[jax.Array], pages: jax.Array,
                       static_scales: Optional[dict] = None) -> dict:
    """Scatter new K/V(-like) tokens into their slots' pages.

    ``new`` maps short key ("k"/"v"/"ckv"/...) -> (B, S, ...) tensor; the
    cache holds it under ``pages_<key>``. Per-key quantization is
    structural: an int8 page array with a ``pages_<key>s`` sibling gets
    per-token dynamic scales computed here; int8 without the sibling uses
    the calibrated per-head scale from ``static_scales``; float pages store
    the cast value. Out-of-range / inactive / unallocated writes are
    dropped (`mode='drop'` keeps -1 indices from wrapping)."""
    ps = kv_cache["pages_pos"].shape[1]
    npages = kv_cache["pages_pos"].shape[0]
    B = kv_cache["pos"].shape[0]
    if positions.ndim == 1:                              # uniform prefill
        pos2 = jnp.broadcast_to(positions[None, :].astype(jnp.int32),
                                (B, positions.shape[0]))
    else:
        pos2 = positions.astype(jnp.int32)               # (B, S) per-row
    S = pos2.shape[1]
    flat = _page_flat_index(pages, pos2, active, ps).reshape(-1)  # (B*S,)
    # ``mode='drop'`` only drops indices >= size; a -1 would WRAP to the
    # pool's last row (NumPy negative indexing) and corrupt whichever slot
    # owns it — map the sentinel to a genuinely out-of-bounds index.
    flat = jnp.where(flat < 0, npages * ps, flat)
    # head-major leaves (K/V pages and their scales) scatter by (page, row)
    page_of, row_of = flat // ps, flat % ps
    out = dict(kv_cache)
    for key, val in new.items():
        leaf = kv_cache["pages_" + key]
        skey = "pages_" + key + "s"
        if leaf.dtype == jnp.int8:
            if skey in kv_cache:                         # per-token dynamic
                amax = jnp.max(jnp.abs(val.astype(jnp.float32)), axis=-1)
                scl = compute_scale_symmetric(amax)      # (B, S, H)
                rows = quantize(val, scl[..., None])
                out[skey] = kv_cache[skey].at[page_of, :, row_of].set(
                    scl.reshape(-1, scl.shape[-1]), mode="drop")
            else:                                        # per-head static
                s = (static_scales or {}).get(key)
                if s is None:
                    raise ValueError(
                        f"int8_per_head KV cache for {key!r} needs a "
                        f"calibrated static scale ({key}c_scale); "
                        f"re-calibrate with kv_cache='int8_per_head' or "
                        f"serve with kv_cache='int8_per_token'")
                rows = quantize(val, s.reshape((1, 1, -1, 1)))
        else:
            rows = val.astype(leaf.dtype)
        if key in HEAD_MAJOR_PAGES:
            out["pages_" + key] = leaf.at[page_of, :, row_of].set(
                rows.reshape((-1,) + rows.shape[2:]), mode="drop")
        else:
            out["pages_" + key] = leaf.reshape(
                (npages * ps,) + leaf.shape[2:]).at[flat].set(
                rows.reshape((-1,) + leaf.shape[2:]),
                mode="drop").reshape(leaf.shape)
    out["pages_pos"] = kv_cache["pages_pos"].reshape(-1) \
        .at[flat].set(pos2.reshape(-1), mode="drop") \
        .reshape(kv_cache["pages_pos"].shape)
    if positions.ndim == 1:
        out["pos"] = kv_cache["pos"] + S
    else:
        act = active if active is not None else jnp.ones((B,), bool)
        out["pos"] = kv_cache["pos"] + act.astype(kv_cache["pos"].dtype)
    return out


def _paged_cache_read(kv_cache: dict, pages: jax.Array, keys, dtype,
                      static_scales: Optional[dict] = None):
    """Gather + dequantize a slot-major view of the paged cache: each
    requested key comes back (B, pages_per_slot * ps, ...), with k_pos
    (B, pages_per_slot * ps) carrying -1 for unallocated pages / unwritten
    entries (the reference XLA decode path; the fused backend's Pallas
    kernel consumes the pages + scales directly instead)."""
    pt = pages.astype(jnp.int32)
    safe = jnp.maximum(pt, 0)                            # gatherable
    B, pps = pt.shape
    ps = kv_cache["pages_pos"].shape[1]
    kpos = jnp.take(kv_cache["pages_pos"], safe, axis=0)  # (B, pps, ps)
    kpos = jnp.where(pt[:, :, None] >= 0, kpos, -1)
    outs = []
    for key in keys:
        leaf = kv_cache["pages_" + key]
        g = jnp.take(leaf, safe, axis=0)                 # (B, pps, ...)
        if key in HEAD_MAJOR_PAGES:                      # -> (B, pps, ps, H, d)
            g = g.swapaxes(2, 3)
        if leaf.dtype == jnp.int8:
            skey = "pages_" + key + "s"
            if skey in kv_cache:
                scl = jnp.take(kv_cache[skey], safe, axis=0).swapaxes(2, 3)
                g = g.astype(jnp.float32) * scl[..., None]
            else:
                s = (static_scales or {})[key]
                g = g.astype(jnp.float32) * s.reshape((1, 1, 1, -1, 1))
        g = g.astype(dtype)
        outs.append(g.reshape((B, pps * ps) + g.shape[3:]))
    return outs, kpos.reshape(B, pps * ps)


def is_paged(kv_cache: Optional[dict]) -> bool:
    return kv_cache is not None and "pages_pos" in kv_cache


def select_state(new: dict, old: dict, active: Optional[jax.Array]):
    """Recurrent-state update gate: rows with active=False keep their old
    state (continuous batching over SSM/hybrid archs)."""
    if active is None:
        return new
    def sel(n, o):
        a = active.reshape((active.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o.astype(n.dtype))
    return jax.tree_util.tree_map(sel, new, old)


def attention_block(x: jax.Array, p: dict, cfg, *, positions: jax.Array,
                    spec: MaskSpec,
                    quant: AttnQuant = AttnQuant(),
                    obs: Optional[dict] = None,
                    kv_cache: Optional[dict] = None,
                    active: Optional[jax.Array] = None,
                    constrain=lambda t, _tag: t,
                    chunk: Optional[int] = None,
                    pages: Optional[jax.Array] = None,
                    backend=None):
    """Full GQA attention block. Returns (out, new_kv_cache|None).

    ``kv_cache`` (decode): {"k": (B, W, Hkv, d), "v": ..., "k_pos": (B, W),
    "pos": (B,)} — W is the cache capacity (a sliding-window ring buffer
    when ``spec.window`` bounds it, else max_seq). The new token's k/v land
    at slot ``pos % W``; ``k_pos`` carries each slot's absolute position so
    :func:`band_mask` handles validity and window eviction. ``positions``
    may be per-row (B, 1) for continuous-batching decode.

    Paged caches (``pages_k``/... keys, see the paged-KV section above)
    take ``pages`` — the scheduler-owned (B, pages_per_slot) page table —
    and store K/V as int8 when the plan's ``kv_cache`` scheme asks for it.
    The fused backend may claim the whole decode-attention step
    (``backend.decode_attention``): a Pallas kernel that gathers pages by
    scalar-prefetched table indices and fuses dequant into the QK^T / PV
    epilogues; the reference path below gathers + dequantizes in XLA and
    reuses :func:`attention_core`, so numerics are backend-independent.
    """
    B, S, _ = x.shape
    observe(obs, "attn_in", x)
    observe_values(obs, "attn_in", x)
    # explicit head sharding after the (q_dim -> H, hd) reshape: without it
    # GSPMD may split the head_dim (contracting in qk^T) and all-reduce the
    # score tensor — measured at +1.8 TB/step on deepseek-coder train_4k
    q = constrain(dense(x, p["wq"], obs=None, backend=backend)
                  .reshape(B, S, cfg.num_heads, cfg.head_dim), "attn_heads")
    k = constrain(dense(x, p["wk"], obs=None, backend=backend)
                  .reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
                  "attn_heads")
    v = constrain(dense(x, p["wv"], obs=None, backend=backend)
                  .reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
                  "attn_heads")
    if cfg.position == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    observe_per_head(obs, "k_cache", k)
    observe_per_head(obs, "v_cache", v)
    new_cache = None
    k_pos = positions
    o = None
    scale = 1.0 / math.sqrt(cfg.head_dim)
    static_sc = {key: p[f"{key}c_scale"] for key in ("k", "v")
                 if f"{key}c_scale" in p}
    if is_paged(kv_cache):
        if pages is None:
            raise ValueError("paged kv_cache requires the page-table "
                             "operand (pages=)")
        new_cache = _paged_cache_write(kv_cache, {"k": k, "v": v},
                                       positions, active, pages, static_sc)
        if S == 1:
            if backend is not None and not quant.enabled:
                o = backend.decode_attention(
                    q, new_cache, pages, positions=positions, active=active,
                    scale=scale, softcap=cfg.attn_softcap,
                    static_scales=static_sc,
                    p_scale=(p.get("p_scale")
                             if quant.plan_scheme == "uint8" else None))
            if o is None:
                (k, v), k_pos = _paged_cache_read(
                    new_cache, pages, ("k", "v"), x.dtype, static_sc)
        # prefill (S > 1): attend over in-sequence K/V, as in the dense path
    elif kv_cache is not None:
        new_cache = _cache_write(kv_cache, {"k": k, "v": v}, positions,
                                 active)
        if S == 1:
            # decode: attend over the (ring) cache
            k = new_cache["k"].astype(x.dtype)
            v = new_cache["v"].astype(x.dtype)
            k_pos = new_cache["k_pos"]
        # prefill (S > 1): attend over in-sequence K/V (the cache may be a
        # ring buffer narrower than S — it only feeds later decode steps)
    if (o is None and kv_cache is None and backend is not None
            and quant.enabled and quant.plan_scheme == "uint8"):
        # fully-quantized encoder core: the fused kernel runs int8 QK^T,
        # the unsigned softmax epilogue and int8 P·V in one pass — and
        # under a norm='int8' span returns the output already requantized
        # (a QuantActivation) at the attn_out GEMM's activation scale
        o = backend.attention(q, k, v, p, k_pos=k_pos, spec=spec,
                              scale=scale, softcap=cfg.attn_softcap)
    if o is None:
        sc = {s: p[f"{s}_scale"] for s in ("q", "k", "p", "v")
              if f"{s}_scale" in p} or None
        o = attention_core(q, k, v, positions, k_pos, spec, scale=scale,
                           attn_softcap=cfg.attn_softcap, quant=quant,
                           scales=sc, obs=obs, constrain=constrain,
                           chunk=chunk)
    o = o.reshape(B, S, cfg.q_dim)
    observe(obs, "attn_out", o)
    observe_values(obs, "attn_out", o)
    out = dense(o, p["wo"], obs=None, backend=backend)
    observe(obs, "attn_delta", out)         # pre-norm site: the residual
    observe_values(obs, "attn_delta", out)  # delta a norm='int8' span carries
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek-v2), with absorbed decode
# ---------------------------------------------------------------------------


def init_mla(key, cfg, dtype=jnp.float32) -> dict:
    m = cfg.mla
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    p = {
        "wkv_a": init_linear(ks[2], cfg.d_model, m.kv_lora_rank + m.qk_rope_dim,
                             False, dtype),
        "kv_norm": init_norm("rmsnorm", m.kv_lora_rank, dtype),
        "wkv_b": init_linear(ks[3], m.kv_lora_rank,
                             cfg.num_heads * (m.qk_nope_dim + m.v_head_dim),
                             False, dtype),
        "wo": init_linear(ks[4], cfg.num_heads * m.v_head_dim, cfg.d_model,
                          False, dtype),
    }
    if m.q_lora_rank:
        p["wq_a"] = init_linear(ks[0], cfg.d_model, m.q_lora_rank, False, dtype)
        p["q_norm"] = init_norm("rmsnorm", m.q_lora_rank, dtype)
        p["wq_b"] = init_linear(ks[1], m.q_lora_rank, cfg.num_heads * qk_dim,
                                False, dtype)
    else:
        p["wq"] = init_linear(ks[0], cfg.d_model, cfg.num_heads * qk_dim,
                              False, dtype)
    return p


def mla_block(x: jax.Array, p: dict, cfg, *, positions: jax.Array,
              spec: MaskSpec, quant: AttnQuant = AttnQuant(),
              obs: Optional[dict] = None,
              kv_cache: Optional[dict] = None,
              active: Optional[jax.Array] = None,
              chunk: Optional[int] = None,
              pages: Optional[jax.Array] = None):
    """Deepseek-v2 MLA. Prefill materializes per-head K/V from the latent;
    decode uses the *absorbed* formulation: attention runs directly in the
    (kv_lora + rope) latent space against a 576-wide cache, and ``wkv_b`` is
    folded into the query/output projections — the cache stays
    ``kv_lora_rank + qk_rope_dim`` per token (the paper-era MLA memory win).
    Returns (out, new_cache|None); cache = {"ckv": (B,S,r), "krope": (B,S,rd),
    "pos": ()}. Paged caches page the latent (``pages_ckv``/``pages_krope``,
    float — the latent is already the compressed representation) through the
    same page table as the standard attention layers.
    """
    m = cfg.mla
    B, S, _ = x.shape
    H, nope, rd, vd = cfg.num_heads, m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim
    observe(obs, "attn_in", x)
    # --- queries -----------------------------------------------------------
    if m.q_lora_rank:
        q_lat = dense(x, p["wq_a"])
        q_lat = rms_norm(q_lat, p["q_norm"])
        observe(obs, "q_lat", q_lat)
        q = dense(q_lat, p["wq_b"])
    else:
        q = dense(x, p["wq"])
    q = q.reshape(B, S, H, nope + rd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, heads_axis=True)
    # --- latent kv ----------------------------------------------------------
    kv = dense(x, p["wkv_a"])
    ckv, k_rope = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    ckv = rms_norm(ckv, p["kv_norm"])
    observe(obs, "c_kv", ckv)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta,
                        heads_axis=False)                    # (B,S,rd) shared
    scale = 1.0 / math.sqrt(nope + rd)
    wkv_b = p["wkv_b"]["w"]
    if isinstance(wkv_b, QuantizedTensor):
        wkv_b_f = wkv_b.dequantize(x.dtype)
    else:
        wkv_b_f = wkv_b.astype(x.dtype)
    wk = wkv_b_f.reshape(m.kv_lora_rank, H, nope + vd)[..., :nope]  # (r,H,nope)
    wv = wkv_b_f.reshape(m.kv_lora_rank, H, nope + vd)[..., nope:]  # (r,H,vd)

    new_cache = None
    paged = is_paged(kv_cache)
    if paged:
        if pages is None:
            raise ValueError("paged kv_cache requires the page-table "
                             "operand (pages=)")
        new_cache = _paged_cache_write(kv_cache,
                                       {"ckv": ckv, "krope": k_rope},
                                       positions, active, pages)
    elif kv_cache is not None:
        new_cache = _cache_write(kv_cache, {"ckv": ckv, "krope": k_rope},
                                 positions, active)
    if new_cache is not None and S == 1:
        if paged:
            (ckv_all, krope_all), cache_kpos = _paged_cache_read(
                new_cache, pages, ("ckv", "krope"), x.dtype)
        else:
            ckv_all = new_cache["ckv"].astype(x.dtype)
            krope_all = new_cache["krope"].astype(x.dtype)
            cache_kpos = new_cache["k_pos"]
        q_pos = positions if positions.ndim == 2 else positions[None]
        mask = band_mask(q_pos, cache_kpos, spec)               # (B|1, S, T)
        # Absorbed decode: q_nope' = q_nope @ wk  → latent space (r).
        q_abs = jnp.einsum("bshn,rhn->bshr", q_nope, wk)
        s = (jnp.einsum("bshr,btr->bhst", q_abs, ckv_all)
             + jnp.einsum("bshr,btr->bhst", q_rope, krope_all)) * scale
        s = jnp.where(mask[:, None], s.astype(jnp.float32), NEG_INF)
        prob = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", prob, ckv_all)     # (B,S,H,r)
        o = jnp.einsum("bshr,rhv->bshv", o_lat, wv)             # (B,S,H,vd)
    else:
        # Prefill: expand per-head keys/values, reuse the shared core
        # (attends over in-sequence K/V; the latent cache was written above).
        k_nope = jnp.einsum("btr,rhn->bthn", ckv, wk)
        v = jnp.einsum("btr,rhv->bthv", ckv, wv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, rd))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        sc = {s_: p[f"{s_}_scale"] for s_ in ("q", "k", "p", "v")
              if f"{s_}_scale" in p} or None
        o = attention_core(qf, k, v, positions, positions, spec, scale=scale,
                           quant=quant, scales=sc, obs=obs, chunk=chunk)
    o = o.reshape(B, S, H * vd)
    observe(obs, "attn_out", o)
    observe_values(obs, "attn_out", o)
    out = dense(o, p["wo"])
    return out, new_cache


# ---------------------------------------------------------------------------
# FFN: GLU (llama/gemma), GELU (bert/hubert)
# ---------------------------------------------------------------------------


def init_ffn(key, cfg, d_ff: Optional[int] = None, dtype=jnp.float32) -> dict:
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.ffn_kind == "glu":
        return {"wg": init_linear(ks[0], cfg.d_model, d_ff, False, dtype),
                "wu": init_linear(ks[1], cfg.d_model, d_ff, False, dtype),
                "wd": init_linear(ks[2], d_ff, cfg.d_model, False, dtype)}
    return {"wi": init_linear(ks[0], cfg.d_model, d_ff, True, dtype),
            "wo": init_linear(ks[1], d_ff, cfg.d_model, True, dtype)}


def ffn_block(x, p: dict, cfg, obs: Optional[dict] = None,
              prefix: str = "", backend=None) -> jax.Array:
    observe(obs, prefix + "ffn_in", x)
    observe_values(obs, prefix + "ffn_in", x)
    if cfg.ffn_kind == "glu":
        h = (dense(x, p["wg"], backend=backend, act="silu")
             * dense(x, p["wu"], backend=backend))
        observe(obs, prefix + "ffn_hidden", h)
        observe_values(obs, prefix + "ffn_hidden", h)
        return dense(h, p["wd"], backend=backend)
    h = dense(x, p["wi"], backend=backend, act="gelu")
    observe(obs, prefix + "ffn_hidden", h)
    observe_values(obs, prefix + "ffn_hidden", h)
    return dense(h, p["wo"], backend=backend)


# ---------------------------------------------------------------------------
# MoE: sort-based capacity-bounded dispatch (TPU-native; no (T,E,C) one-hot)
# ---------------------------------------------------------------------------


def init_moe(key, cfg, dtype=jnp.float32) -> dict:
    mo = cfg.moe
    ks = jax.random.split(key, 5)
    E, D, F = mo.num_experts, cfg.d_model, mo.d_ff_expert
    std = 1.0 / math.sqrt(D)
    p = {
        "router": {"w": jax.random.normal(ks[0], (D, E), jnp.float32) * std},
        "wg": {"w": jax.random.normal(ks[1], (E, D, F), dtype) * std},
        "wu": {"w": jax.random.normal(ks[2], (E, D, F), dtype) * std},
        "wd": {"w": jax.random.normal(ks[3], (E, F, D), dtype)
               / math.sqrt(F)},
    }
    if mo.num_shared:
        p["shared"] = init_ffn(ks[4], cfg, d_ff=mo.d_ff_expert * mo.num_shared,
                               dtype=dtype)
    return p


def _expert_gemm(xe: jax.Array, w, xs: Optional[jax.Array],
                 obs: Optional[dict], site: str, backend=None) -> jax.Array:
    """Batched per-expert GEMM: xe (..., E, C, D) @ w (E, D, F) ->
    (..., E, C, F); the optional leading axis is the token-shard group.
    Quantized experts hold per-expert-per-channel weight scales (E, 1, N)
    (2-D blocks) or, under the v4 ``experts`` family, per-expert static
    activation scales ``xs`` shaped (E, 1, 1). ``backend`` may claim the
    op via ``expert_gemm`` (the fused per-expert quant_linear path) or
    decline, keeping this reference einsum."""
    eq = ("gecd,edf->gecf" if xe.ndim == 4 else "ecd,edf->ecf")
    observe(obs, site, xe)
    if backend is not None and isinstance(w, QuantizedTensor):
        y = backend.expert_gemm(xe, w, xs)
        if y is not None:
            return y.astype(xe.dtype)
    if isinstance(w, QuantizedTensor):
        if xs is not None:
            xq = QuantizedTensor(quantize(xe, xs), xs, None)
        else:
            xq = quantize_per_token(xe)
        acc = jnp.einsum(eq, xq.values, w.values,
                         preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * xq.scale * w.scale).astype(xe.dtype)
    return jnp.einsum(eq, xe, w.astype(xe.dtype))


def _dispatch_one(xt, logits, E, K, C, obs_unused=None):
    """Sort-based capacity dispatch for ONE token group.
    xt: (Tl, D); logits: (Tl, E). Returns (xe (E, C, D), st, sg, keep, slot)
    for the combine step."""
    Tl = xt.shape[0]
    gates, idx = jax.lax.top_k(logits, K)                        # (Tl, K)
    gates = jax.nn.softmax(gates, axis=-1)
    flat_expert = idx.reshape(-1)                                # (Tl*K,)
    flat_token = jnp.repeat(jnp.arange(Tl), K)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert)                             # stable
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    ones = jnp.ones_like(se)
    pos_in_expert = jax.lax.associative_scan(jnp.add, ones) - 1
    seg_start = jnp.searchsorted(se, jnp.arange(E))              # (E,)
    pos_in_expert = pos_in_expert - seg_start[se]
    keep = pos_in_expert < C
    slot = se * C + jnp.where(keep, pos_in_expert, 0)            # (Tl*K,)
    src = jnp.where(keep[:, None], xt[st], 0)
    xe = jnp.zeros((E * C, xt.shape[1]), xt.dtype).at[slot].add(src)
    return xe.reshape(E, C, xt.shape[1]), st, sg, keep, slot


def _combine_one(ye, st, sg, keep, slot, Tl, D, dtype):
    contrib = jnp.where(keep[:, None],
                        ye.reshape(-1, D)[slot] * sg[:, None].astype(dtype),
                        0)
    return jnp.zeros((Tl, D), dtype).at[st].add(contrib)


def moe_block(x: jax.Array, p: dict, cfg, obs: Optional[dict] = None,
              constrain: Callable[[jax.Array, str], jax.Array] = lambda a, _: a,
              backend=None) -> jax.Array:
    """Top-k MoE with capacity-bounded sort-based dispatch.

    Router (always float — it is tiny and precision-critical) picks top-k
    experts per token; tokens are routed into per-expert capacity buffers via
    an argsort over expert ids (the TPU-native alternative to the (T, E, C)
    one-hot einsum, which does not fit memory at 160 experts), batched
    expert GEMMs run over (E, C, D), and results scatter-add back with the
    gate weights. Overflowing tokens are dropped (capacity factor bounds the
    buffer — standard Switch/MaxText semantics).

    **Distribution**: sort/gather/scatter with data-dependent indices cannot
    cross a sharded axis without GSPMD replicating the (T*K, D) routed
    tensor (measured: 5 all-reduces of 128 GB per MoE layer). So the
    dispatch runs per *token group* — a leading axis aligned with the data
    shards (``constrain`` exposes ``dsize``) — vmapped so every index op is
    group-local; the cross-shard movement then happens only in the dense
    expert GEMM (weight all-gather or token all-to-all, GSPMD's choice),
    which is the production EP dataflow. Capacity becomes per-(shard,
    expert), matching real all-to-all MoE systems.

    ``constrain`` lets the distribution layer pin intermediate shardings
    without this module importing mesh machinery.
    """
    mo = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = mo.num_experts, mo.top_k
    groups = getattr(constrain, "dsize", 1)
    if T % max(groups, 1) or groups <= 1:
        groups = 1
    Tl = T // groups
    C = max(1, int(math.ceil(mo.capacity_factor * Tl * K / E)))
    observe(obs, "ffn_in", x)
    xg = constrain(x.reshape(groups, Tl, D), "moe_tokens")
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"]["w"])                        # f32 router

    xe, st, sg, keep, slot = jax.vmap(
        lambda xt, lg: _dispatch_one(xt, lg, E, K, C))(xg, logits)
    xe = constrain(xe, "moe_dispatch")                  # (G, E, C, D)
    observe_per_expert(obs, "expert_in", xe)

    # --- expert GEMMs (GLU) --------------------------------------------------
    h = (jax.nn.silu(_expert_gemm(xe, p["wg"]["w"], p["wg"].get("xs"),
                                  obs, "ffn_in_e", backend=backend))
         * _expert_gemm(xe, p["wu"]["w"], p["wu"].get("xs"), None, "ffn_in_e",
                        backend=backend))
    h = constrain(h, "moe_hidden")
    observe(obs, "ffn_hidden", h)
    observe_per_expert(obs, "expert_hidden", h)
    ye = _expert_gemm(h, p["wd"]["w"], p["wd"].get("xs"), None, "ffn_hidden",
                      backend=backend)
    ye = constrain(ye, "moe_dispatch")                  # (G, E, C, D)

    # --- combine (group-local scatter) ----------------------------------------
    y = jax.vmap(lambda yg, sti, sgi, ki, sli: _combine_one(
        yg, sti, sgi, ki, sli, Tl, D, x.dtype))(ye, st, sg, keep, slot)
    y = y.reshape(T, D)
    if "shared" in p:
        y = y + ffn_block(x, p["shared"], cfg, obs=obs,
                          prefix="shared_", backend=backend).reshape(T, D)
    return y.reshape(B, S, D)


# ---------------------------------------------------------------------------
# causal temporal conv (RG-LRU / xLSTM blocks)
# ---------------------------------------------------------------------------


def init_conv1d(key, width: int, channels: int, dtype=jnp.float32) -> dict:
    return {"w": jax.random.normal(key, (width, channels), dtype)
            / math.sqrt(width),
            "b": jnp.zeros((channels,), dtype)}


def causal_conv1d(x: jax.Array, p: dict,
                  state: Optional[jax.Array] = None):
    """Depthwise causal conv over time. x: (B, S, C); state: (B, W-1, C)
    carries the left context for decode. Returns (y, new_state)."""
    W = p["w"].shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)                   # (B, S+W-1, C)
    y = sum(xp[:, i:i + x.shape[1], :] * p["w"][i].astype(x.dtype)
            for i in range(W))
    y = y + p["b"].astype(x.dtype)
    new_state = xp[:, -(W - 1):, :] if W > 1 else pad
    return y, new_state


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------


def init_embeddings(key, cfg, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    p = {"tok": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                  dtype) * 0.02}
    if cfg.position == "learned":
        p["pos"] = jax.random.normal(ks[1], (cfg.max_position, cfg.d_model),
                                     dtype) * 0.02
    if cfg.num_segments:
        p["seg"] = jax.random.normal(ks[2], (cfg.num_segments, cfg.d_model),
                                     dtype) * 0.02
    if cfg.frontend is not None:
        p["frontend_proj"] = init_linear(ks[3], cfg.frontend_dim, cfg.d_model,
                                         True, dtype)
    if cfg.norm_kind == "layernorm" and cfg.family == "bert":
        p["emb_norm"] = init_norm("layernorm", cfg.d_model, dtype)
    return p


def embed(tokens: jax.Array, p: dict, cfg, *, positions: jax.Array,
          segments: Optional[jax.Array] = None,
          compute_dtype=jnp.bfloat16, backend=None) -> jax.Array:
    """Fused token(+segment)(+position) embedding — the paper's Tensor-fusion
    target. A fused backend routes learned-position archs through the Pallas
    ``fused_embed`` kernel (one HBM pass); otherwise three XLA gathers."""
    if backend is not None:
        y = backend.embed(tokens, p, cfg, positions=positions,
                          segments=segments, compute_dtype=compute_dtype)
        if y is not None:
            return y
    x = jnp.take(p["tok"], tokens, axis=0).astype(compute_dtype)
    if "pos" in p:
        x = x + jnp.take(p["pos"], positions, axis=0).astype(compute_dtype)
    if "seg" in p and segments is not None:
        x = x + jnp.take(p["seg"], segments, axis=0).astype(compute_dtype)
    if cfg.emb_scale_by_sqrt_dim:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), compute_dtype)
    if "emb_norm" in p:
        x = layer_norm(x, p["emb_norm"])
    return x
