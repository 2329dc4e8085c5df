"""Reduction of a profiler trace to device busy time, per-operation device
time and idle gaps attributed to what the host was doing.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` alone. Device operations are the events of the
``XLA Ops`` lines of the ``/device:TPU:<n>`` planes; host activities are the
benchmark's own ``bench.<what>`` spans on the host planes. Both are on the
trace's one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

HOST_PREFIX = "bench."
#: no benchmark span lasts longer (a step, an encode call, a wait); the
#: attribution looks back no further for an enclosing one
MAX_SPAN_S = 5.0
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str
    start: float            # seconds on the trace clock
    dur: float
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    devices: dict           # plane name -> [Op] in start order
    spans: list             # (name, start, end) of the host's bench spans

    def ops(self) -> list:
        return [op for ops in self.devices.values() for op in ops]


def find(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "plugins",
                                          "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    out = {}
    for item in ev.stats:
        try:
            k, v = item
        except (TypeError, ValueError):
            continue
        out[str(k)] = v
    return out


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = [Op(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                          _stats(ev)) for ev in line.events]
                devices[plane.name] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name[len(HOST_PREFIX):], s,
                                      s + ev.duration_ns * 1e-9))
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


def busy_intervals(ops) -> list:
    """Union of the operations' intervals, in start order."""
    out: list = []
    for op in sorted(ops, key=lambda o: o.start):
        if out and op.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], op.end)
        else:
            out.append([op.start, op.end])
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.devices:
        return 0.0
    per = [sum(e - s for s, e in busy_intervals(ops))
           for ops in trace.devices.values()]
    return sum(per) / len(per)


def short_name(name: str) -> str:
    """``%quant_linear.47 = f32[...] custom-call(...)`` -> ``quant_linear``:
    the HLO instruction's name without its number."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def leaf_ops(ops) -> list:
    """The operations that hold no other: a loop (``while``) is listed on
    the same line as the operations of its body, around them."""
    ops = sorted(ops, key=lambda o: o.start)
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= op.end or nxt.end > op.end]


def op_seconds(trace: Trace) -> dict:
    """Device seconds by operation (short name), summed over the devices."""
    out: dict = {}
    for ops in trace.devices.values():
        for op in leaf_ops(ops):
            k = short_name(op.name)
            out[k] = out.get(k, 0.0) + op.dur
    return out


def kernel_ops(trace: Trace, kernel: str) -> list:
    """Every event of one kernel (by short name), over the devices."""
    return [op for op in trace.ops() if short_name(op.name) == kernel]


DTYPE_BYTES = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2, "s16": 2,
               "f32": 4, "s32": 4, "u32": 4}


def shapes(name: str) -> list:
    """(dtype, dims) of an HLO op's result, then of each operand, from the
    event's name, which is the instruction's text."""
    body = name.split(" = ", 1)[1].split("custom_call_target", 1)[0]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", body)]


def idle_by_activity(trace: Trace) -> dict:
    """Idle device seconds between operations (first device), attributed
    to the innermost host span around the middle of each gap; ``other``
    where no span covers it."""
    if not trace.devices:
        return {}
    first = next(iter(trace.devices.values()))
    iv = busy_intervals(first)
    starts = [s[1] for s in trace.spans]
    out: dict = {}
    for (_, a), (b, _) in zip(iv, iv[1:]):
        mid = (a + b) / 2
        name = "other"
        # the latest-starting span that is still open at ``mid``
        for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if trace.spans[k][2] >= mid:
                name = trace.spans[k][0]
                break
            if mid - trace.spans[k][1] > MAX_SPAN_S:
                break
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
