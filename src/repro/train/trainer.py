"""Distributed training loop: pjit + FSDP/TP sharding, remat, grad
accumulation, atomic checkpoint/auto-resume, straggler monitoring, and
optional int8-compressed cross-pod gradient all-reduce.

The step function is a single pjit'd program: loss -> grads ->
(optional pod-axis compressed all-reduce) -> AdamW update. Shardings come
from repro.distributed.sharding.Rules; optimizer moments inherit the param
specs (ZeRO-3). The loop tolerates kill-at-any-step: checkpoints are atomic
(repro.checkpoint.store) and the data pipeline is counter-indexed, so
resume = load newest checkpoint + fast-forward the step counter.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import store
from repro.configs.base import ArchConfig
from repro.core.plan import PrecisionPlan
from repro.distributed.sharding import Rules
from repro.distributed import compression
from repro.models import transformer as T
from repro.train.optimizer import AdamW, AdamWState, global_norm


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    keep_last: int = 3
    grad_accum: int = 1
    remat: bool = True
    compute_dtype: str = "bfloat16"
    compress_pod_grads: bool = False       # int8 DCN all-reduce (beyond-paper)
    straggler_factor: float = 2.0          # step slower than f x median -> log


class TrainState:
    def __init__(self, params, opt_state: AdamWState, err_state=None):
        self.params = params
        self.opt_state = opt_state
        self.err_state = err_state          # error feedback (compression)

    def as_tree(self):
        t = {"params": self.params, "opt": self.opt_state._asdict()}
        if self.err_state is not None:
            t["err"] = self.err_state
        return t

    @classmethod
    def from_tree(cls, t):
        return cls(t["params"], AdamWState(**t["opt"]), t.get("err"))


class Trainer:
    def __init__(self, cfg: ArchConfig, policy: PrecisionPlan, *,
                 mesh: Optional[Mesh] = None, optimizer: AdamW = AdamW(),
                 tcfg: TrainConfig = TrainConfig(),
                 scheme: T.QuantScheme = T.QuantScheme(),
                 loss_fn: Optional[Callable] = None,
                 head: Optional[tuple] = None):
        self.cfg = cfg
        self.policy = policy
        self.plan = T.build_plan(cfg, policy)
        self.mesh = mesh
        self.optimizer = optimizer
        self.tcfg = tcfg
        self.scheme = scheme
        self.head = head
        self.rules = Rules(cfg, mesh) if mesh is not None else None
        self.loss_fn = loss_fn or T.lm_loss
        self._step_times: list[float] = []

    # -- state ----------------------------------------------------------------
    def init_state(self, key, dtype=jnp.float32) -> TrainState:
        params = T.init_params(key, self.cfg, self.policy, head=self.head,
                               dtype=dtype)
        opt = self.optimizer.init(params)
        err = (compression.init_error_state(params)
               if self.tcfg.compress_pod_grads else None)
        if self.rules is not None:
            shardings = self.rules.params_sharding(params)
            params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, s), params, shardings)
        return TrainState(params, opt, err)

    # -- compiled step ----------------------------------------------------------
    def make_step(self, jit: bool = True):
        cfg, plan, scheme = self.cfg, self.plan, self.scheme
        tcfg, opt = self.tcfg, self.optimizer
        constrain = (self.rules if self.rules is not None
                     else (lambda x, _t: x))
        cdtype = jnp.dtype(tcfg.compute_dtype)
        mesh, rules = self.mesh, self.rules

        def loss_of(params, batch):
            kw = {}
            if rules is not None:
                lead = batch.get("tokens", batch.get("frames"))
                kw["chunk"] = rules.attn_chunk(lead.shape[0], lead.shape[1],
                                               cfg.num_heads)
            return self.loss_fn(params, batch, cfg, plan, scheme,
                                constrain=constrain, remat=tcfg.remat,
                                compute_dtype=cdtype, **kw)

        def step(params, opt_state, err_state, batch):
            if tcfg.grad_accum > 1:
                def micro(carry, mb):
                    loss_acc, grad_acc = carry
                    l, g = jax.value_and_grad(loss_of)(params, mb)
                    return (loss_acc + l,
                            jax.tree_util.tree_map(jnp.add, grad_acc, g)), None
                zero = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                mb = jax.tree_util.tree_map(
                    lambda x: x.reshape((tcfg.grad_accum,
                                         x.shape[0] // tcfg.grad_accum)
                                        + x.shape[1:]), batch)
                (loss, grads), _ = jax.lax.scan(micro, (0.0, zero), mb)
                n = float(tcfg.grad_accum)
                loss = loss / n
                grads = jax.tree_util.tree_map(lambda g: g / n, grads)
            else:
                loss, grads = jax.value_and_grad(loss_of)(params, batch)
            if err_state is not None and mesh is not None \
                    and "pod" in mesh.axis_names:
                grads, err_state = compression.compress_allreduce_pytree(
                    grads, err_state, mesh=mesh,
                    specs=rules.params_spec(params), axis="pod")
            gnorm = global_norm(grads)
            params2, opt_state2 = opt.update(grads, opt_state, params)
            return params2, opt_state2, err_state, \
                {"loss": loss, "grad_norm": gnorm}

        if not jit:
            return step
        return jax.jit(step, donate_argnums=(0, 1, 2))

    # -- the loop ------------------------------------------------------------
    def fit(self, state: TrainState, next_batch: Callable[[int], dict],
            *, start_step: int = 0, log=print) -> TrainState:
        """Run tcfg.steps steps. ``next_batch(i)`` supplies global batch i
        (counter-indexed => restart-safe). Auto-resumes from the newest
        checkpoint in tcfg.checkpoint_dir when one exists."""
        tcfg = self.tcfg
        step_fn = self.make_step()
        i = start_step
        if tcfg.checkpoint_dir:
            latest = store.latest_step(tcfg.checkpoint_dir)
            if latest is not None and latest > i:
                state = TrainState.from_tree(store.restore(
                    tcfg.checkpoint_dir, latest, state.as_tree()))
                i = latest
                log(f"[trainer] resumed from step {latest}")
        while i < tcfg.steps:
            batch = next_batch(i)
            t0 = time.perf_counter()
            params, opt_state, err, metrics = step_fn(
                state.params, state.opt_state, state.err_state, batch)
            metrics = jax.device_get(metrics)
            dt = time.perf_counter() - t0
            state = TrainState(params, opt_state, err)
            i += 1
            self._note_step_time(dt, i, log)
            if i % tcfg.log_every == 0:
                log(f"[trainer] step {i} loss={float(metrics['loss']):.4f} "
                    f"gnorm={float(metrics['grad_norm']):.3f} dt={dt:.3f}s")
            if tcfg.checkpoint_dir and i % tcfg.checkpoint_every == 0:
                store.save(tcfg.checkpoint_dir, i, state.as_tree(),
                           keep_last=tcfg.keep_last)
        if tcfg.checkpoint_dir:
            store.save(tcfg.checkpoint_dir, i, state.as_tree(),
                       keep_last=tcfg.keep_last)
        return state

    def _note_step_time(self, dt: float, step: int, log) -> None:
        """Straggler monitor: flag steps >> the running median (on real
        fleets this feeds the controller that evicts slow hosts)."""
        self._step_times.append(dt)
        hist = self._step_times[-50:]
        if len(hist) >= 10:
            med = float(np.median(hist))
            if dt > self.tcfg.straggler_factor * med:
                log(f"[trainer] STRAGGLER step {step}: {dt:.3f}s vs median "
                    f"{med:.3f}s")
