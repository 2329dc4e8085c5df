"""Readers of the program's own serving phases and queue waits.

The engines time each phase of a step as a ``samp.<engine>.<phase>`` host
span (``repro.serve.metrics.Phases``; ``engine`` is ``enc`` or ``dec``) and
keep the phase's seconds and calls in ``Runtime.stats`` (``phase_s``,
``phase_n``); the schedulers stamp each request with the seconds it waited
in the queue (``queue_wait``) and the step that flushed or admitted it
(``step``). The readers here take the counters between the window's open
and its close (the ``before``/``after`` snapshots of ``Runtime.stats``),
the queue waits of the requests due before the profiler started, and the
``samp.`` spans from a ``--trace 1`` run's profile.

A program without these counters and spans gives None, never 0.
"""
from __future__ import annotations

import sys

import readers
import stats
import tracereduce

PREFIX = "samp."
#: the phases of a step that are host work: all but ``fetch``, the wait for
#: the device's results and their copy to the host
HOST = {"enc": ("flush", "assemble", "pad", "dispatch", "predict"),
        "dec": ("admit", "drain", "pages", "assemble", "dispatch", "sample")}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _span(engine: str, phase: str) -> str:
    return f"{PREFIX}{engine}.{phase}"


def phase_table(run):
    """(seconds, calls) by span name over the window, or None where the
    program keeps no phase table."""
    before, after = run.window.counters["before"], run.window.counters["after"]
    if "phase_s" not in after:
        return None
    secs = {k: v - before["phase_s"].get(k, 0.0)
            for k, v in after["phase_s"].items()}
    calls = {k: v - before["phase_n"].get(k, 0)
             for k, v in after["phase_n"].items()}
    return secs, calls


def ms_per_step(run, engine: str, phases) -> float | None:
    """Milliseconds in ``phases`` per executable dispatch (one encoder
    micro-batch, one model tick of the decoder)."""
    table = phase_table(run)
    if table is None:
        return None
    secs, calls = table
    steps = calls.get(_span(engine, "dispatch"), 0)
    if not steps:
        return None
    return 1e3 * sum(secs.get(_span(engine, p), 0.0) for p in phases) / steps


def host_ms(run, engine: str) -> float | None:
    """Host milliseconds per dispatch in the step's host phases; logs the
    whole phase table."""
    table = phase_table(run)
    if table is not None:
        secs, calls = table
        for name in sorted(k for k in secs if k.startswith(_span(engine, ""))):
            if calls[name]:
                log(f"[phases] {name}: {calls[name]} calls, "
                    f"{1e3 * secs[name] / calls[name]:.4f} ms each, "
                    f"{secs[name]:.4f} s in all")
    return ms_per_step(run, engine, HOST[engine])


def fetch_ms(run, engine: str) -> float | None:
    return ms_per_step(run, engine, ("fetch",))


def queue_wait_ms(run) -> float | None:
    """Mean milliseconds the program counted a request queued before its
    flush or admission, over the finished requests due before the profiler
    started (``readers._untraced_end``)."""
    end = readers._untraced_end(run)
    xs = [r.req.queue_wait for r in run.window.records
          if r.ok and r.item.due < end
          and getattr(r.req, "step", None) is not None]
    return 1e3 * stats.mean(xs) if xs else None


def samp_spans(path) -> list:
    """(name, start, end) of the program's ``samp.`` spans on the host
    planes of a profile, in start order, on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = ev.start_ns * 1e-9
                    spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return sorted(spans, key=lambda s: s[1])


def _profile():
    """The profile that ``run.py`` wrote for this ``--trace 1`` run."""
    from run import TRACE_DIR
    return tracereduce.find(TRACE_DIR)


def host_bound_share(run, engine: str, path=None) -> float | None:
    """Share of the traced window, in percent, in which the device is idle
    between operations while the host is inside one of ``engine``'s
    ``samp.`` spans and not in its ``fetch`` phase. Logs how
    ``tracereduce.idle_by_activity`` gives each gap to the innermost
    ``samp.`` or ``bench.`` span around its middle."""
    if run.trace is None or not run.trace.devices \
            or run.window.trace_at is None:
        return None
    if path is None:
        try:
            path = _profile()
        except FileNotFoundError:
            return None
    spans = samp_spans(path)
    host = host_bound_seconds(run.trace.devices, spans, engine)
    if host is None:
        return None
    _log_coverage(run.trace, spans)
    a, b = run.window.trace_at
    return 100.0 * host / (b - a)


def host_bound_seconds(devices, spans, engine: str) -> float | None:
    """Idle device seconds between operations (first device) that lie
    inside one of ``engine``'s spans but not inside its ``fetch`` phase
    (the wait for the device and the copy of its results); None where
    ``spans`` holds none of the engine's. A gap is split where a phase
    ends: a decode tick's gap is the tail of the logits copy in ``fetch``
    and then the next tick's host phases."""
    mine = [s for s in spans if s[0].startswith(_span(engine, ""))]
    if not mine:
        return None
    gaps = _gaps(devices)
    fetch = [s for s in mine if s[0] == _span(engine, "fetch")]
    return _overlap(gaps, _union(mine)) - _overlap(gaps, _union(fetch))


def _gaps(devices) -> list:
    busy = tracereduce.busy_intervals(next(iter(devices.values())))
    return [(a, b) for (_, a), (b, _) in zip(busy, busy[1:])]


def _union(spans) -> list:
    return tracereduce.busy_intervals(
        [tracereduce.Op(n, s, e - s, {}) for n, s, e in spans])


def _overlap(a, b) -> float:
    """Seconds shared by two lists of disjoint intervals in start order."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _log_coverage(trace, spans) -> None:
    both = sorted(spans + [("bench." + n, s, e) for n, s, e in trace.spans],
                  key=lambda s: s[1])
    idle = tracereduce.idle_by_activity(tracereduce.Trace(trace.devices,
                                                          both))
    total = sum(idle.values())
    outside = sum(v for k, v in idle.items()
                  if not k.startswith(PREFIX) and k != "bench.wait")
    for name, secs in tracereduce.top(idle, 20):
        log(f"[phases] idle under {name}: {secs:.6f} s")
    took: dict = {}
    for span in both:
        took.setdefault(span[0], []).append(span)
    gaps = _gaps(trace.devices)
    for name, xs in sorted(took.items()):
        log(f"[phases] traced span {name}: {len(xs)} calls, "
            f"{1e3 * stats.mean([e - s for _, s, e in xs]):.4f} ms each, "
            f"device idle inside it {_overlap(gaps, _union(xs)):.6f} s")
    if total:
        log(f"[phases] idle between operations {total:.6f} s, "
            f"{100 * outside / total:.2f}% of it outside every samp. span "
            f"and bench.wait")
