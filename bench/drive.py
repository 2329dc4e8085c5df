"""The open loop: offers a schedule to an engine for a window, follows the
requests due in it to completion, and records every time it takes.

The window drives the engines with the calls ``serve/frontend/driver.py``'s
``EngineDriver`` makes (``submit`` then ``step``; the encoder's
micro-batcher ages requests on its own ``time.monotonic`` clock, as in the
server). Every latency is timed from the request's scheduled due time, not
from when the loop got to it. The benchmark's own spans
(``jax.profiler.TraceAnnotation``, named ``bench.<what>``) mark what the
host is doing, so that a traced run can say what the device waited for.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Optional

import jax

#: how long the loop sleeps while a micro-batch ages (the front end's
#: driver ticks at the same granularity)
POLL_S = 0.0005

#: JAX events that mean something was traced or compiled
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Record:
    item: object
    submitted: float = math.nan          # host clock, window-relative
    admitted: float = math.nan           # decode: start of its first tick
    served: float = math.nan             # encoder: start of its encode call
    done: float = math.nan               # last output on the host
    token_times: list = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    req: object = None

    @property
    def ok(self) -> bool:
        return self.error is None and not math.isnan(self.done)


@dataclasses.dataclass
class Window:
    seconds: float
    records: list
    steps: list            # (start, end, info) of each model step
    compiles: int = 0
    compiled: list = dataclasses.field(default_factory=list)
    trace_at: Optional[tuple] = None     # (start, stop) of the profile
    counters: dict = dataclasses.field(default_factory=dict)
    opened: float = math.nan             # host clock when the window opened


class CompileCounter:
    """Counts JAX trace and compile events between ``start`` and ``stop``."""

    def __init__(self):
        self.n = 0
        self.on = False
        self.where: list = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **kw):
        if self.on and name in COMPILE_EVENTS:
            self.n += 1
            self.where.append(kw.get("fun_name", name))


class Tracer:
    """Profiles ``[start, start + length)`` of the window when ``path`` is
    set; otherwise does nothing."""

    def __init__(self, path, start: float, length: float):
        self.path, self.start, self.length = path, start, length
        self.state = "off" if path is None else "pending"
        self.at = None

    def poll(self, now: float, clock) -> None:
        if self.state == "pending" and now >= self.start:
            jax.profiler.start_trace(str(self.path))
            self.state, self.began = "on", clock()
        elif self.state == "on" and now >= self.began + self.length:
            self.stop(clock)

    def stop(self, clock) -> None:
        if self.state == "on":
            end = clock()
            jax.profiler.stop_trace()
            self.at = (self.began, end)
            self.state = "done"


def _span_factory(enabled: bool):
    """``span(name)``: a ``bench.<name>`` host span in the profiler's trace
    when tracing, nothing otherwise."""
    if not enabled:
        return lambda _name: contextlib.nullcontext()
    return lambda name: jax.profiler.TraceAnnotation(f"bench.{name}")


def run(system, items, seconds: float, drain_s: float, *,
        trace_dir=None, trace_start: float = 0.0, trace_len: float = 0.0,
        counter: Optional[CompileCounter] = None) -> Window:
    """Offer ``items`` for ``seconds``, then follow them for at most
    ``drain_s`` more seconds."""
    encoder = system.cell.kind == "encoder"
    eng = system.engine
    span = _span_factory(trace_dir is not None)
    tracer = Tracer(trace_dir, trace_start, trace_len)
    records = [Record(it) for it in items]
    steps: list = []
    # what set-up made, the schedule and its records with it, stays out of
    # the collections that the window's allocations trigger
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    clock = lambda: time.perf_counter() - t0            # noqa: E731
    win = Window(seconds, records, steps, opened=t0)
    win.counters["before"] = dict(eng.runtime.stats)
    if counter is not None:
        counter.n, counter.on, counter.where = 0, True, []
    step = _encoder_loop if encoder else _decode_loop
    try:
        step(eng, records, steps, clock, span, tracer, seconds + drain_s)
    finally:
        tracer.stop(clock)
        gc.unfreeze()
        if counter is not None:
            counter.on = False
            win.compiles = counter.n
            win.compiled = counter.where[:10]
    win.counters["after"] = dict(eng.runtime.stats)
    win.trace_at = tracer.at
    for r in records:
        if r.error is None and math.isnan(r.done):
            r.error = f"not finished {drain_s:g}s after the window"
    return win


def _submit_due(eng, records, i, now, clock, span, make):
    while i < len(records) and records[i].item.due <= now:
        rec = records[i]
        rec.req = make(rec.item)
        with span("admit"):
            try:
                eng.submit(rec.req)
            except ValueError as e:          # refused by the engine
                rec.error = f"refused: {e}"
        rec.submitted = clock()
        i += 1
    return i


def _encoder_loop(eng, records, steps, clock, span, tracer, hard):
    from repro.serve import EncoderRequest
    make = lambda it: EncoderRequest(uid=it.uid, tokens=it.tokens,  # noqa
                                     segments=it.segments)
    by_uid = {r.item.uid: r for r in records}
    rt = eng.runtime
    inner = rt.encode
    calls: list = []

    def timed_encode(params, inputs, lengths=None):
        t = clock()
        with span("encode"):
            out = inner(params, inputs, lengths)
        calls.append((t, clock(), [int(n) for n in lengths]))
        return out
    rt.encode = timed_encode
    try:
        i = 0
        while True:
            now = clock()
            tracer.poll(now, clock)
            i = _submit_due(eng, records, i, now, clock, span, make)
            queued = len(eng.batcher)
            if (i == len(records) and not queued) or now >= hard:
                return
            if queued:
                first = len(calls)
                with span("step"):
                    retired = eng.step()
                done = clock()
                if retired:
                    k = 0
                    for start, end, lengths in calls[first:]:
                        steps.append((start, end, lengths))
                        for _ in lengths:
                            rec = by_uid[retired[k].uid]
                            rec.served, rec.done = start, done
                            k += 1
                    continue
            nxt = records[i].item.due if i < len(records) else hard
            wait = min(nxt - clock(), POLL_S) if queued else nxt - clock()
            if wait > 0:
                with span("wait"):
                    time.sleep(wait)
    finally:
        del rt.encode                        # back to the class's method


def _decode_loop(eng, records, steps, clock, span, tracer, hard):
    from repro.serve import Request
    make = lambda it: Request(uid=it.uid, prompt=it.tokens,  # noqa: E731
                              max_tokens=it.max_tokens)
    by_uid = {r.item.uid: r for r in records}
    seen: dict = {}                       # uid -> ticks in a slot so far
    i = 0
    while True:
        now = clock()
        tracer.poll(now, clock)
        i = _submit_due(eng, records, i, now, clock, span, make)
        busy = eng.sched.busy
        if (i == len(records) and not busy) or now >= hard:
            return
        if not busy:
            nxt = records[i].item.due
            with span("wait"):
                time.sleep(max(0.0, nxt - clock()))
            continue
        start = clock()
        with span("step"):
            retired = eng.step()
        end = clock()
        ran = [r for r in eng.sched.active if r is not None] + retired
        positions = []
        for req in ran:
            rec = by_uid.get(req.uid)
            if rec is None:
                continue
            n = seen.get(req.uid, 0)
            if n == 0:
                rec.admitted = start
            positions.append(n)
            seen[req.uid] = n + 1
            while len(rec.token_times) < len(req.output):
                rec.token_times.append(end)
            if req.done:
                rec.done = end
        if positions:
            steps.append((start, end, positions))
