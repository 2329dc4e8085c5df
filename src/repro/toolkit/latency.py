"""Latency backends + the analytic TPU-v5e roofline model.

This is the latency axis of the SAMP tradeoff (Table 2, Figure 3), behind a
swappable backend interface in the ``LATENCY_BACKENDS`` registry:

* ``roofline``  — analytic: every GEMM and bandwidth-bound elementwise pass
  of one encoder layer is priced as

      t_op = max(flops / peak_rate(precision), bytes / hbm_bw)

  and summed over the layer inventory given the per-layer SAMP mode. The
  only latency source available on this CPU-only container.
* ``wallclock`` — measured: jits the real forward for each candidate plan
  and times it (median of ``reps``). The paper's setting on real hardware.

Both produce the same ``(qparams, plan, precision) -> seconds`` callable the
sweep consumes, so the allocator is agnostic to the source (DESIGN.md §2).

Hardware constants (TPU v5e): 197 TFLOP/s bf16, 394 TOP/s int8 (2x),
~49 TFLOP/s fp32 (no MXU fp32 path — priced at bf16/4), 819 GB/s HBM.
The model reproduces the paper's qualitative shape: each Quant-FFN-Only
layer buys a few percent end-to-end (the paper measures 2-3% on T4).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

from repro.configs.base import ArchConfig
from repro.core.plan import LayerMode, PrecisionPlan
from repro.core.samp import LatencyFn
from repro.toolkit.registry import register_latency_backend

PEAK = {"float32": 49.25e12, "bfloat16": 197e12, "float16": 197e12,
        "int8": 394e12}
HBM_BW = 819e9
BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    flops: float
    bytes: float
    precision: str

    @property
    def seconds(self) -> float:
        return max(self.flops / PEAK[self.precision], self.bytes / HBM_BW)


def _gemm(name: str, m: int, k: int, n: int, precision: str) -> Op:
    b = BYTES[precision]
    # activations in + weights + activations out (out in same precision for
    # int8 inter-layer dataflow; float otherwise)
    byts = m * k * b + k * n * b + m * n * b
    return Op(name, 2.0 * m * k * n, byts, precision)


def _elementwise(name: str, elems: int, passes: int, precision: str) -> Op:
    return Op(name, elems, passes * elems * BYTES[precision], precision)


def layer_ops(cfg: ArchConfig, mode: LayerMode, batch: int, seq: int,
              float_dtype: str = "bfloat16") -> list[Op]:
    """GEMM + bandwidth inventory of ONE encoder layer under ``mode``."""
    T = batch * seq
    D = cfg.d_model
    mha_p = "int8" if mode.quant_mha else float_dtype
    ffn_p = "int8" if mode.quant_ffn else float_dtype
    ops: list[Op] = []
    # --- MHA group ----------------------------------------------------------
    if cfg.attention != "none":
        ops += [_gemm("wq", T, D, cfg.q_dim, mha_p),
                _gemm("wk", T, D, cfg.kv_dim, mha_p),
                _gemm("wv", T, D, cfg.kv_dim, mha_p),
                _gemm("wo", T, cfg.q_dim, D, mha_p)]
        # batched score/value matmuls: window-bounded if sliding
        kv_len = min(seq, cfg.sliding_window) \
            if cfg.attention == "sliding" else seq
        H, hd = cfg.num_heads, cfg.head_dim
        ops.append(Op("qk^T", 2.0 * batch * H * seq * kv_len * hd,
                      batch * H * seq * kv_len * BYTES[mha_p], mha_p))
        ops.append(Op("pv", 2.0 * batch * H * seq * kv_len * hd,
                      batch * H * seq * kv_len * BYTES[mha_p], mha_p))
        ops.append(_elementwise("softmax", batch * H * seq * kv_len, 3,
                                float_dtype))
    # --- FFN group -----------------------------------------------------------
    d_ff = cfg.d_ff or int(cfg.proj_factor * D) * 2
    n_mats = 3 if cfg.ffn_kind == "glu" else 2
    if cfg.moe is not None:
        # active expert compute per token: top_k routed + shared
        f = cfg.moe.d_ff_expert
        act = cfg.moe.top_k + cfg.moe.num_shared
        ops += [_gemm(f"moe_up[{act}]", T * act, D, f, ffn_p),
                _gemm(f"moe_gate[{act}]", T * act, D, f, ffn_p),
                _gemm(f"moe_down[{act}]", T * act, f, D, ffn_p)]
    elif d_ff:
        for i in range(n_mats - 1):
            ops.append(_gemm(f"ffn_in{i}", T, D, d_ff, ffn_p))
        ops.append(_gemm("ffn_out", T, d_ff, D, ffn_p))
    # --- norms/residuals (always bandwidth-bound, float) ---------------------
    ops.append(_elementwise("norms+residual", T * D, 6, float_dtype))
    return ops


def encoder_latency(cfg: ArchConfig, precision: PrecisionPlan, *,
                    batch: int, seq: int, chips: int = 1) -> float:
    """Modeled seconds for one forward pass of the whole encoder stack,
    priced via the plan's per-layer derived modes."""
    total = 0.0
    for mode in precision.modes:
        for op in layer_ops(cfg, mode, batch, seq, precision.float_dtype):
            total += op.seconds
    return total / chips


def layer_latency(cfg: ArchConfig, mode: LayerMode, *, batch: int, seq: int,
                  float_dtype: str = "bfloat16") -> float:
    return sum(op.seconds
               for op in layer_ops(cfg, mode, batch, seq, float_dtype))


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------


class LatencyBackend:
    """A latency source. ``bind`` closes over the measurement point (model
    config, batch geometry, an example batch for measured backends) and
    returns the ``(qparams, plan, precision) -> seconds`` callable that
    :meth:`repro.core.samp.SAMPEngine.sweep` consumes."""

    name = "?"

    def bind(self, cfg: ArchConfig, *, batch: int, seq: int,
             example_batch: Optional[dict] = None, scheme=None,
             compute_dtype=None) -> LatencyFn:
        raise NotImplementedError


@register_latency_backend("roofline")
class RooflineBackend(LatencyBackend):
    """Analytic roofline estimate; ignores params entirely."""

    name = "roofline"

    def __init__(self, chips: int = 1):
        self.chips = chips

    def bind(self, cfg, *, batch, seq, example_batch=None, scheme=None,
             compute_dtype=None) -> LatencyFn:
        def fn(qparams, plan, precision: PrecisionPlan) -> float:
            return encoder_latency(cfg, precision, batch=batch, seq=seq,
                                   chips=self.chips)
        return fn


@register_latency_backend("wallclock")
class WallclockBackend(LatencyBackend):
    """Measured wall-clock of the jitted forward, per candidate plan.
    Each (mode, k) candidate is its own compiled executable (the paper's
    "configure the result to the toolkit" semantics), so compile time is
    excluded via warmup and the median of ``reps`` timed runs is reported."""

    name = "wallclock"

    def __init__(self, reps: int = 5, warmup: int = 1):
        self.reps = reps
        self.warmup = warmup

    def bind(self, cfg, *, batch, seq, example_batch=None, scheme=None,
             compute_dtype=None) -> LatencyFn:
        import jax
        import jax.numpy as jnp
        from repro.models import transformer as T

        scheme = scheme or T.QuantScheme()
        compute_dtype = compute_dtype or jnp.float32
        if example_batch is None:
            example_batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(0), (batch, seq), 0, cfg.vocab_size)}
            if cfg.num_segments:
                example_batch["segments"] = jnp.zeros((batch, seq), jnp.int32)
        example_batch = {k: jnp.asarray(v) for k, v in example_batch.items()}

        def fn(qparams, plan, precision: PrecisionPlan) -> float:
            @jax.jit
            def fwd(p, b):
                h, _ = T.forward(p, b, cfg, plan, scheme,
                                 compute_dtype=compute_dtype,
                                 return_hidden=True)
                return h
            for _ in range(max(self.warmup, 1)):
                fwd(qparams, example_batch).block_until_ready()
            times = []
            for _ in range(self.reps):
                t0 = time.perf_counter()
                fwd(qparams, example_batch).block_until_ready()
                times.append(time.perf_counter() - t0)
            times.sort()
            return times[len(times) // 2]
        return fn
