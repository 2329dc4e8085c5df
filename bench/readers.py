"""Shared arithmetic of the per-layer readers in ``bench/layer_metrics/``.

A reader takes the run (``run.RunData``) and returns a number, or None
where the run holds nothing to read; it never returns 0 for a share of a
roofline or of a peak.
"""
from __future__ import annotations

import math
import sys

import modelops
import spec
import stats
import tracereduce


def _untraced_end(run) -> float:
    """Host-clock metrics are read from the window before the profiler
    starts: starting it stalls the loop for about a second, and the queue
    that builds then is the tracer's, not the system's."""
    at = run.window.trace_at
    return at[0] if at is not None else math.inf


def queue_ms(run, field: str):
    """Mean milliseconds from due to ``field`` (a Record time), over the
    requests due before the profiler starts."""
    end = _untraced_end(run)
    xs = [getattr(r, field) - r.item.due for r in run.window.records
          if r.ok and not math.isnan(getattr(r, field)) and r.item.due < end]
    return 1e3 * stats.mean(xs) if xs else None


def _untraced_steps(run) -> list:
    end = _untraced_end(run)
    return [s for s in run.window.steps if s[1] < end]


def step_ms(run):
    xs = [b - a for a, b, _ in _untraced_steps(run)]
    return 1e3 * stats.mean(xs) if xs else None


def idle_share(run):
    if run.trace is None or not run.trace.devices:
        return None
    a, b = run.window.trace_at
    return 100.0 * (1.0 - tracereduce.busy_seconds(run.trace) / (b - a))


def pad_share(run):
    before, after = run.window.counters["before"], run.window.counters["after"]
    real = after["real_tokens"] - before["real_tokens"]
    pad = after["padded_tokens"] - before["padded_tokens"]
    return 100.0 * pad / (real + pad) if real + pad else None


def occupancy(run):
    steps = _untraced_steps(run)
    if not steps:
        return None
    slots = run.config["engine"]["slots"]
    return 100.0 * stats.mean([len(pos) / slots for _, _, pos in steps])


def mfu(run):
    """Least time at the chip's peaks of the model operations of the real
    tokens of the steps in the traced part of the window, over the summed
    host-clock time of those steps."""
    steps = run.steps_in_trace()
    if not steps:
        return None
    c = run.config
    least = 0.0
    for _, _, info in steps:
        for x in info:
            ops = (modelops.encoder_request(c, x) if run.cell.kind == "encoder"
                   else modelops.decode_token(c, x))
            least += modelops.least_seconds(ops, run.peaks)
    took = sum(b - a for a, b, _ in steps)
    return 100.0 * least / took


def roofline(run, kernel: str, least):
    """A kernel's share of its roofline over the traced window, in percent:
    the least time the chip could take for the kernel's calls (each call's
    ``least(op)`` -> (ops seconds at peak, bytes seconds at HBM bandwidth))
    over the summed device time of its events. Logs which bound held."""
    if run.trace is None:
        return None
    ops = tracereduce.kernel_ops(run.trace, kernel)
    bounds = [least(op) for op in ops]
    if not ops or any(b is None for b in bounds):
        return None
    need = sum(max(b) for b in bounds)
    by_bytes = sum(max(b) for b in bounds if b[1] >= b[0])
    took = sum(op.dur for op in ops)
    print(f"[roofline] {kernel}: {len(ops)} calls, {took:.6f} s on the "
          f"device, least {need:.6f} s, {100 * by_bytes / need:.1f}% of it "
          f"bound by HBM bytes", file=sys.stderr)
    return 100.0 * need / took


def _bytes(dtype: str) -> int:
    return tracereduce.DTYPE_BYTES[dtype]


def quant_linear_roofline(run):
    ql, p = spec.kernel_counts("quant_linear"), run.peaks

    def least(op):
        (rt, (m, n)), (_, (_, k)) = tracereduce.shapes(op.name)[:2]
        return (ql.ops(m, k, n) / p[ql.PEAK],
                ql.bytes_moved(m, k, n, _bytes(rt)) / p["hbm_bytes_per_s"])
    return roofline(run, "quant_linear", least)


def flash_attention_roofline(run):
    fa, p = spec.kernel_counts("flash_attention"), run.peaks

    def least(op):
        sh = tracereduce.shapes(op.name)
        (rt, _), (_, (bh, sq, hd)), (_, (_, sk, _)) = sh[:3]
        b = sh[4][1][0]                          # key positions (b, 1, sk)
        h = bh // b
        return (fa.ops(b, h, sq, sk, hd) / p[fa.PEAK],
                fa.bytes_moved(b, h, sq, sk, hd, _bytes(rt))
                / p["hbm_bytes_per_s"])
    return roofline(run, "quant_flash_attention", least)


def decode_attention_roofline(run):
    """The live context of a call is the host's: the mean, over the ticks
    in the traced window, of the keys the live slots attend over."""
    da, p = spec.kernel_counts("decode_attention"), run.peaks
    ticks = run.steps_in_trace()
    if not ticks:
        return None
    tokens = stats.mean([sum(x + 1 for x in pos) for _, _, pos in ticks])

    def least(op):
        (_, (slots, kv_heads, group, hd)) = tracereduce.shapes(op.name)[0]
        heads = kv_heads * group
        return (da.ops(tokens, heads, hd) / p[da.PEAK],
                da.bytes_moved(tokens, slots, heads, kv_heads, hd)
                / p["hbm_bytes_per_s"])
    return roofline(run, "decode_attention", least)
