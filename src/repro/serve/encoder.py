"""Encoder serving engine — the paper's primary workload, served.

SAMP's headline setting is batched text processing on BERT-style encoders
(CLUE classification / pair matching / sequence labeling). This engine
serves those requests through the same layered runtime the decode engine
uses:

* admission is a :class:`~repro.serve.scheduler.MicroBatcher` — per-length-
  bucket queues with max-batch and max-wait flushing, so similar-length
  requests batch together and no request waits unboundedly;
* execution is a :class:`~repro.serve.runtime.Runtime` — each flushed
  micro-batch is padded to its (batch, length) bucket and run through the
  cached executable with pad-mask-correct attention, so a mixed-length
  request stream compiles at most once per bucket and a request's logits
  are identical whether it is served alone or inside a full batch;
* the target head comes from the ``TARGETS`` registry (cls /
  pair_matching / seq_labeling / lm), so any registered head serves
  without engine changes.
"""
from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.serve.runtime import Runtime
from repro.serve.scheduler import EncoderRequest, MicroBatcher


class EncoderServeEngine:
    """Dynamic micro-batching server for encoder workloads."""

    def __init__(self, cfg: ArchConfig, params, plan, *,
                 target: Union[str, object] = "cls",
                 scheme: T.QuantScheme = T.QuantScheme(),
                 max_batch: int = 8, max_wait: float = 0.0,
                 max_len: int = 256, compute_dtype=jnp.float32,
                 runtime: Optional[Runtime] = None,
                 backend="reference", mesh=None, router=None):
        # ``backend`` names the compute backend (repro.kernels.backend) for
        # the engine's Runtime, ``mesh`` the serving mesh its executables
        # are placed over; both ignored when a runtime is shared in.
        # ``router`` (a repro.adaptive.PlanRouter) makes serving
        # input-adaptive: requests are clustered at admission and each
        # cluster-pure micro-batch runs its cluster's (params, plan)
        # through a per-cluster Runtime sibling.
        if isinstance(target, str):
            # lazy: repro.toolkit imports repro.serve for the facade
            from repro.toolkit.registry import get_target
            target = get_target(target)
        if target.name != "lm" and "head" not in params:
            raise ValueError(
                f"target {target.name!r} needs head params; build them via "
                f"Pipeline.init_params or TargetSpec.init")
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.target = target
        self.max_len = max_len
        self.runtime = runtime or Runtime(
            cfg, plan, scheme=scheme, compute_dtype=compute_dtype,
            head=lambda p, h: target.apply(p, h, cfg),
            token_level=target.token_level, max_len=max_len,
            backend=backend, mesh=mesh)
        self.batcher = MicroBatcher(max_batch=max_batch, max_wait=max_wait,
                                    max_len=max_len)
        self.router = router
        if router is not None and not router.bound:
            router.bind(self.runtime)
        self._stats = {"requests": 0, "steps": 0, "batches": 0,
                       "retired": 0, "batched_rows": 0}

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: EncoderRequest,
               now: Optional[float] = None) -> None:
        if len(req.tokens) == 0:
            raise ValueError("empty request")
        if len(req.tokens) > self.max_len:
            raise ValueError(f"request length {len(req.tokens)} exceeds "
                             f"max_len {self.max_len}")
        if req.segments is not None and len(req.segments) != len(req.tokens):
            raise ValueError("segments length must match tokens")
        if self.router is not None:
            self.router.admit(req)      # stamps req.cluster before queueing
        self.batcher.submit(req, now)
        self._stats["requests"] += 1

    # -- the serving loop ----------------------------------------------------
    def step(self, now: Optional[float] = None,
             force: bool = False) -> list[EncoderRequest]:
        """Serve every micro-batch that is due; returns retired requests.
        Each phase is a ``samp.enc.<phase>`` span and counter (see
        :class:`~repro.serve.metrics.Phases`) inside ``samp.enc.step``,
        which carries the step's ``step`` number."""
        self._stats["steps"] += 1
        n = self._stats["steps"]
        phase = self.runtime.phases
        retired: list[EncoderRequest] = []
        with phase("samp.enc.step", step=n):
            with phase("samp.enc.flush"):
                due = self.batcher.ready(now, force=force)
            for blen, reqs in due:
                with phase("samp.enc.assemble"):
                    runtime, params, inputs, lengths = self._assemble(blen,
                                                                      reqs)
                logits = runtime.encode(params, inputs, lengths)
                with phase("samp.enc.predict"):
                    for i, req in enumerate(reqs):
                        row = logits[i]
                        if self.target.token_level:
                            row = row[:int(lengths[i])]
                        req.logits = row
                        # the registered head's own decision rule (argmax
                        # for the built-ins; custom TargetSpecs may
                        # override)
                        req.prediction = np.asarray(self.target.predict(row))
                        req.step = n
                        req.done = True
                        retired.append(req)
                B = len(reqs)
                self._stats["batches"] += 1
                self._stats["batched_rows"] += B
                self._stats["retired"] += B
        return retired

    def _assemble(self, blen: int, reqs: list[EncoderRequest]):
        """(runtime, params, inputs, lengths) of one micro-batch."""
        B = len(reqs)
        tokens = np.zeros((B, blen), np.int32)
        segments = np.zeros((B, blen), np.int32)
        lengths = np.zeros((B,), np.int32)
        for i, req in enumerate(reqs):
            n = len(req.tokens)
            tokens[i, :n] = req.tokens
            if req.segments is not None:
                segments[i, :n] = req.segments
            lengths[i] = n
        inputs = {"tokens": tokens}
        if self.cfg.num_segments:
            inputs["segments"] = segments
        if self.router is None:
            return self.runtime, self.params, inputs, lengths
        # batches are cluster-pure by construction (the MicroBatcher keys
        # queues on (bucket, cluster)), so one entry serves all
        entry = self.router.entry(reqs[0].cluster)
        return entry.runtime, entry.params, inputs, lengths

    def run(self, now: Optional[float] = None) -> list[EncoderRequest]:
        """Drain the queues (force-flush partial buckets too)."""
        return self.step(now, force=True)

    @property
    def stats(self) -> dict:
        # unified counters surface shared with /metrics — see
        # serve.metrics.engine_counters
        from repro.serve.metrics import engine_counters
        s = dict(self._stats)
        s.update({f"runtime_{k}": v for k, v in self.runtime.stats.items()
                  if k not in ("buckets", "phase_s", "phase_n")})
        s.update(engine_counters(self))
        return s
