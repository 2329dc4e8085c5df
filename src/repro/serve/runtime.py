"""Bucketed executable runtime — the one compilation cache for inference.

Every inference path (the token-level serving engine, the encoder serving
engine, ``Pipeline.predict``/``eval``, and the wall-clock benchmarks) funnels
through one :class:`Runtime`, which owns the jitted executables keyed by
``(backend_name, precision_fingerprint, mesh_fingerprint, kind,
bucket_shape)``:

* a Runtime instance is bound to one ``(cfg, plan, scheme, compute_dtype,
  head)`` configuration — but the executable-cache key leads with the
  deployment's scheme identity: the bound
  :class:`~repro.core.plan.PrecisionPlan`'s stable ``fingerprint()`` (or a
  structural hash of (plan, scheme) when no PrecisionPlan was given), so
  :meth:`share` can hand sibling views of one cache to pipelines running
  *different* plans without key collisions. The compute-backend name
  (reference / fused / auto — :mod:`repro.kernels.backend`) leads the key:
  one plan compiles to different executables per backend;
* request shapes are rounded up to power-of-two *buckets* (batch and, for
  token inputs, sequence length), so a mixed-length request stream compiles
  at most once per bucket instead of once per shape;
* padded positions are masked **inside** the executable: per-row position
  ids carry ``-1`` on padding, which :func:`repro.models.layers.band_mask`
  excludes from attention (its cache-validity check), so a padded forward
  matches the natural-shape forward for the real rows/positions.

Parameters are call arguments, not trace constants — fine-tuning or swapping
quantized weights of the same structure reuses the compiled executables.

The ``stats`` counters make the caching auditable: ``traces`` increments
inside the traced function body (a Python side effect that only runs when
XLA actually re-traces), so a serving log can *prove* "≤ 1 compile per
(plan, scheme, bucket)" rather than assume it.

MoE configs are the one exception to bucketing: expert capacity is derived
from the token count, so padding would change routing for real rows. They
run at natural shapes (still cached per shape, still counted).

**Mesh-aware serving.** A Runtime bound to a ``mesh=`` (a ``jax.sharding``
Mesh with ``data``/``model`` axes) places every executable over that mesh:

* params/batch/cache shardings come from the same
  :class:`~repro.distributed.sharding.Rules` engine training uses, with
  ``fsdp=False`` — inference replicates params over ``data`` (pure DP on
  the batch) and tensor-parallelizes over ``model``. Quantized leaves need
  no extra rules: int8 ``values`` inherit the weight's spec, per-channel
  scales shard along the same output axis, per-tensor scales / zero
  points / ``xs`` activation scales replicate;
* the executable-cache key gains the mesh topology fingerprint next to
  the backend name and plan fingerprint, so one shared cache serves
  deployments on different topologies without collisions;
* batch buckets round up to multiples of the dp axis size (after the
  power-of-two rounding), so every compiled batch splits evenly over
  ``data`` — no padded batch sharding;
* on a mesh whose model axis is 1, each executable runs its whole step
  per device (``shard_map``): a device computes exactly what the unmeshed
  runtime computes for its rows, Pallas kernels included, and each holds
  its own share of the decode caches (the paged pool too: the engine's
  :class:`~repro.serve.scheduler.PagePool` hands a slot only pages of its
  own device). With a model axis above 1, GSPMD partitions the step and
  the fused backend (:meth:`ComputeBackend.with_mesh`) declines every op.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T

HeadFn = Callable[[dict, jax.Array], jax.Array]     # (params, hidden)->logits


def _tree_sig(tree) -> int:
    """Stable signature of a pytree's jit-relevant structure (leaf shapes +
    dtypes + treedef). Two calls with different signatures would make one
    ``jax.jit`` entry silently re-trace, so the executable cache folds this
    into its key to keep ``traces <= executables`` honest."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return hash((treedef,
                 tuple((jnp.shape(l), jnp.result_type(l)) for l in leaves)))


def bucket_size(n: int, floor: int = 1, cap: Optional[int] = None) -> int:
    """Smallest power of two >= n (and >= floor); clamped to ``cap`` when the
    cap itself can hold ``n``."""
    if n <= 0:
        raise ValueError(f"bucket_size needs n >= 1, got {n}")
    b = max(int(floor), 1)
    while b < n:
        b *= 2
    if cap is not None and cap >= n:
        b = min(b, cap)
    return b


class Runtime:
    """Jitted-executable cache for one (cfg, plan, scheme) deployment.

    ``head`` is the target stage: ``(full_params, hidden) -> logits`` (a
    :class:`~repro.toolkit.targets.TargetSpec.apply`, ``T.unembed``, ...);
    ``None`` returns the final-norm hidden states. ``token_level`` marks
    per-position outputs so :meth:`encode` can slice padding back off.
    """

    def __init__(self, cfg: ArchConfig, plan, *,
                 scheme: T.QuantScheme = T.QuantScheme(),
                 precision=None,
                 compute_dtype=jnp.float32,
                 head: Optional[HeadFn] = None, token_level: bool = False,
                 min_batch: int = 1, min_len: int = 8,
                 max_len: Optional[int] = None,
                 chunk: Optional[int] = T.DEFAULT_CHUNK,
                 backend="reference", mesh=None,
                 cluster: Optional[int] = None):
        from repro.distributed.sharding import Rules, mesh_fingerprint
        from repro.kernels.backend import get_backend
        self.cfg = cfg
        self.plan = plan
        self.scheme = scheme
        self.precision = precision          # Optional[PrecisionPlan]
        self.compute_dtype = compute_dtype
        self.head = head
        self.token_level = token_level
        self.min_batch = min_batch
        self.min_len = min_len
        self.max_len = max_len
        self.chunk = chunk
        # mesh-aware deployments shard params/batches via the training
        # Rules engine with fsdp off (inference: replicate params over
        # 'data', TP over 'model').
        self.mesh = mesh
        self.rules = Rules(cfg, mesh, fsdp=False) if mesh is not None \
            else None
        # MoE expert capacity scales with the token count: padded tokens
        # would consume capacity and change routing for real rows.
        self.bucketed = cfg.moe is None
        # On a mesh that splits only the batch (model axis 1), every
        # executable runs its whole step once per device (shard_map): each
        # device runs the unmeshed program on its rows, so meshed results
        # equal unmeshed ones at the per-device batch, and the Pallas
        # kernels need no partitioning. Elsewhere (a model axis above 1;
        # MoE, whose unbucketed batches need not split) GSPMD partitions
        # the step, and the fused backend declines every op.
        self.per_device = (mesh is not None and self.bucketed
                           and self.rules.msize == 1)
        self._backend_arg = backend
        self.backend = get_backend(backend)
        if mesh is not None and not self.per_device:
            self.backend = self.backend.with_mesh(mesh)
        # the scheme-identity half of every cache key: the compute backend's
        # name, the PrecisionPlan's stable fingerprint when one is bound
        # (else a structural hash of (execution plan, scheme)), and the
        # mesh topology fingerprint — all shareable across sibling views.
        # Each component exists because the same plan compiles to
        # *different* executables per backend (reference XLA vs fused
        # Pallas) AND per mesh topology (different shardings, different
        # collectives), so neither switch may collide. ``cluster`` (the
        # adaptive-routing dimension, None for unrouted deployments) keeps
        # two clusters distinct even when per-cluster autotune landed
        # byte-identical plans — their calibrated scales still differ, so
        # a routed deployment always holds exactly K entries per bucket.
        self.cluster = cluster
        self._plan_key = (self.backend.name,
                          precision.fingerprint() if precision is not None
                          else hash((plan, scheme)),
                          mesh_fingerprint(mesh),
                          cluster)
        self._exe: dict[tuple, Callable] = {}
        # argument shapes of the call that built each executable, so
        # :meth:`executables` can hand back the compiled programs
        self._arg_shapes: dict[tuple, tuple] = {}
        self._stats = {"calls": 0, "traces": 0,
                       "real_tokens": 0, "padded_tokens": 0}

    def share(self, plan, *, scheme: Optional[T.QuantScheme] = None,
              precision=None, backend=None, mesh="inherit",
              cluster: Optional[int] = None) -> "Runtime":
        """A sibling Runtime bound to a different (plan, scheme, precision,
        backend, mesh) that SHARES this runtime's executable cache and
        counters. Cache keys lead with (backend name, precision
        fingerprint, mesh fingerprint), so two pipelines under different
        plans — or the same plan on different compute backends or mesh
        topologies — share one runtime without key collisions, and still
        compile at most once per (backend, plan, mesh, kind, bucket).
        ``mesh`` defaults to this runtime's mesh; pass ``None`` to get an
        explicitly unmeshed sibling. ``cluster`` tags the sibling with a
        traffic-cluster id (adaptive routing): the cache key grows that
        dimension, so each cluster's member plan owns its own executables
        even when plan content coincides."""
        rt = Runtime(self.cfg, plan, scheme=scheme or self.scheme,
                     precision=precision, compute_dtype=self.compute_dtype,
                     head=self.head, token_level=self.token_level,
                     min_batch=self.min_batch, min_len=self.min_len,
                     max_len=self.max_len, chunk=self.chunk,
                     backend=backend or self._backend_arg,
                     mesh=self.mesh if mesh == "inherit" else mesh,
                     cluster=cluster)
        rt._exe = self._exe
        rt._arg_shapes = self._arg_shapes
        rt._stats = self._stats
        return rt

    # -- cache plumbing ------------------------------------------------------
    def _get(self, key: tuple, build: Callable[[], Callable],
             shardings: Optional[Callable[[], tuple]] = None) -> Callable:
        # ``shardings`` is a thunk so cache hits never pay the spec-tree
        # walk — it only runs when an executable is actually created
        fn = self._exe.get(key)
        if fn is None:
            if shardings is None:
                fn = jax.jit(build())
            else:
                in_s, out_s = shardings()
                fn = jax.jit(build(), in_shardings=in_s, out_shardings=out_s)
            self._exe[key] = fn
        return fn

    def _note_args(self, key: tuple, args: tuple) -> None:
        if key not in self._arg_shapes:
            self._arg_shapes[key] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                               jnp.result_type(a)), args)

    def executables(self):
        """``(key, jax.stages.Compiled)`` for every executable this cache
        has run, lowered at the shapes of the call that built it. JAX's
        trace and compile caches return the program already built, so
        this neither retraces nor recompiles; the compiled text shows
        which Pallas kernels (``tpu_custom_call``) an executable holds."""
        for key, fn in self._exe.items():
            if key in self._arg_shapes:
                yield key, fn.lower(*self._arg_shapes[key]).compile()

    @property
    def _dp(self) -> int:
        """Batch-sharding factor of the bound mesh (1 when unmeshed)."""
        return self.rules.dp_size if self.rules is not None else 1

    @property
    def shards(self) -> int:
        """Devices that each run the step on their own share of the batch
        and of the decode caches (1 unless ``per_device``)."""
        return self._dp if self.per_device else 1

    def _sharding(self, spec) -> "jax.sharding.NamedSharding":
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, spec)

    @property
    def identity(self) -> dict:
        """The deployment-identity triple every executable-cache key leads
        with, as strings — the /metrics endpoint exports these as the
        ``samp_build_info`` labels."""
        from repro.distributed.sharding import mesh_fingerprint
        fp = self._plan_key[1]
        out = {"backend": self.backend.name,
               "plan": fp if isinstance(fp, str)
               else f"structural:{fp & 0xFFFFFFFFFFFFFFFF:016x}",
               "mesh": mesh_fingerprint(self.mesh)}
        if self.cluster is not None:
            out["cluster"] = str(self.cluster)
        return out

    @property
    def stats(self) -> dict:
        """Counters + executable census. ``traces`` counts actual XLA traces
        (incremented inside the traced body); ``executables`` the distinct
        (plan, kind, bucket) entries. Keys are
        ("encode", plan_key, Bb, Sb, ...) / ("decode", plan_key, B, ...)."""
        return dict(self._stats, executables=len(self._exe),
                    buckets=sorted({(k[0],) + (k[2:4] if k[0] == "encode"
                                               else k[2:3])
                                    for k in self._exe}))

    # -- encoder / full-sequence path ---------------------------------------
    def _build_encode(self):
        cfg, plan, scheme = self.cfg, self.plan, self.scheme
        head, compute_dtype, chunk = self.head, self.compute_dtype, self.chunk
        backend = self.backend
        constrain_kw = {} if self.rules is None or self.per_device else \
            {"constrain": self.rules}

        def fn(params, inputs, lengths):
            self._stats["traces"] += 1          # trace-time side effect
            if cfg.frontend == "audio":
                S = inputs["frames"].shape[1]
            else:
                S = inputs["tokens"].shape[1]
            P = (inputs["prefix_embeds"].shape[1]
                 if cfg.frontend == "vision" and "prefix_embeds" in inputs
                 else 0)
            idx = jnp.arange(S + P, dtype=jnp.int32)
            valid = idx[None, :] < (lengths + P)[:, None]       # (B, S+P)
            # -1 on padding: band_mask's validity check drops these keys, so
            # real rows attend only over their true tokens
            positions = jnp.where(valid, idx[None], -1)
            x = T.embed_inputs(params, inputs, cfg,
                               positions=jnp.maximum(positions, 0),
                               compute_dtype=compute_dtype, backend=backend)
            x, _ = T.run_groups(x, params, cfg, plan, scheme,
                                positions=positions, chunk=chunk,
                                backend=backend, **constrain_kw)
            x = L.norm(x, params["final_norm"], cfg.norm_kind)
            return head(params, x) if head is not None else x
        if not self.per_device:
            return fn
        from jax.sharding import PartitionSpec as P
        rows = P(self.rules.axes.dp)
        return jax.shard_map(fn, mesh=self.mesh, in_specs=(P(), rows, rows),
                             out_specs=rows, check_vma=False)

    def _encode_shardings(self, params, padded: dict, lengths) -> tuple:
        """(in_shardings, out_shardings) for one encode executable: params
        from the rule table, inputs/lengths batch-sharded over dp, the
        (batch-leading) output sharded over dp when the bucket divides."""
        from jax.sharding import PartitionSpec
        r = self.rules
        in_s = (r.params_sharding(params),
                r.batch_sharding(padded),
                r.batch_sharding({"lengths": lengths})["lengths"])
        B = lengths.shape[0]
        out_s = self._sharding(
            PartitionSpec(r.axes.dp) if B % r.dp_size == 0
            else PartitionSpec())
        return in_s, out_s

    def encode(self, params, inputs: dict,
               lengths: Optional[np.ndarray] = None) -> np.ndarray:
        """Full-sequence forward through the bucketed cache.

        ``inputs`` maps input name -> (B, S, ...) array (numpy or jax);
        ``lengths`` (B,) gives each row's true token count (default: the
        full width — no ragged padding). Pads to the (batch, length) bucket,
        runs the cached executable, and slices the result back to the true
        batch (and true length for token-level heads).
        """
        arrs = {k: np.asarray(v) for k, v in inputs.items()}
        lead = arrs.get("tokens", arrs.get("frames"))
        B, S = lead.shape[0], lead.shape[1]
        if lengths is None:
            lengths = np.full((B,), S, np.int32)
        lengths = np.asarray(lengths, np.int32)
        seq_bucketed = self.bucketed and "tokens" in arrs
        Bb = bucket_size(B, self.min_batch) if self.bucketed else B
        if self.bucketed and Bb % self._dp:
            # meshed serving: the compiled batch must split evenly over the
            # data axis, so buckets round up to dp multiples (a non-power-
            # of-two dp size yields non-power-of-two buckets, still cached)
            Bb = -(-Bb // self._dp) * self._dp
        Sb = (bucket_size(S, self.min_len, self.max_len) if seq_bucketed
              else S)
        padded = {}
        for k, v in arrs.items():
            pad = [(0, Bb - B)] + [(0, 0)] * (v.ndim - 1)
            if k in ("tokens", "segments"):
                pad[1] = (0, Sb - v.shape[1])
            padded[k] = np.pad(v, pad)
        full_len = np.zeros((Bb,), np.int32)
        full_len[:B] = lengths
        # input structure (which arrays, their dtypes) and the params
        # structure (float vs quantized leaves) are part of the compiled
        # signature: distinct signatures get distinct cache entries
        fn_key = ("encode", self._plan_key, Bb, Sb, _tree_sig(padded),
                  _tree_sig(params))
        fn = self._get(fn_key, self._build_encode,
                       shardings=None if self.rules is None else
                       (lambda: self._encode_shardings(params, padded,
                                                       full_len)))
        args = (params, {k: jnp.asarray(v) for k, v in padded.items()},
                jnp.asarray(full_len))
        self._note_args(fn_key, args)
        out = fn(*args)
        self._stats["calls"] += 1
        self._stats["real_tokens"] += int(lengths.sum())
        self._stats["padded_tokens"] += Bb * Sb - int(lengths.sum())
        out = np.asarray(jax.device_get(out))
        out = out[:B]
        if self.token_level and out.ndim >= 2:
            P = (arrs["prefix_embeds"].shape[1]
                 if self.cfg.frontend == "vision" and "prefix_embeds" in arrs
                 else 0)
            out = out[:, :P + S]
        return out

    # -- decode / token-level path ------------------------------------------
    def _build_decode(self):
        cfg, plan, scheme = self.cfg, self.plan, self.scheme
        compute_dtype, backend = self.compute_dtype, self.backend
        constrain_kw = {} if self.rules is None or self.per_device else \
            {"constrain": self.rules}

        def step(params, caches, tokens, pos, active, pages):
            logits, caches = T.decode_step(
                params, tokens, caches, pos, cfg, plan, scheme,
                active=active, compute_dtype=compute_dtype, pages=pages,
                backend=backend, **constrain_kw)
            return logits[:, -1, :], caches

        def fn(params, caches, tokens, pos, active, pages):
            self._stats["traces"] += 1          # trace-time side effect
            if not self.per_device:
                return step(params, caches, tokens, pos, active, pages)
            from jax.sharding import PartitionSpec as P
            dp = self.rules.axes.dp
            rows = (tokens, pos, active) + (() if pages is None else (pages,))

            def local(params, caches, tokens, pos, active, pages=None):
                if pages is not None:
                    # the table holds pool-wide page ids; this device holds
                    # pages [i * n, (i + 1) * n) of the pool
                    n = T.kv_geometry(caches)[2]
                    base = jax.lax.axis_index(dp) * n
                    pages = jnp.where(pages >= 0, pages - base, -1)
                return step(params, caches, tokens, pos, active, pages)
            return jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(None, dp)) + (P(dp),) * len(rows),
                out_specs=(P(dp), P(None, dp)),
                check_vma=False)(params, caches, *rows)
        return fn

    def _decode_shardings(self, params, caches) -> tuple:
        """(in_shardings, out_shardings) for one decode executable: params
        from the rule table, caches batch/head-sharded per the cache rules,
        per-tick operands (tokens/pos/active/page table) replicated — they
        are tiny — and the caches come back under the same shardings they
        went in. Run per device, every cache leaf (per-slot rows and the
        page pool alike) and every per-tick operand splits over the data
        axes instead."""
        from jax.sharding import PartitionSpec
        r = self.rules
        if self.per_device:
            rows = self._sharding(PartitionSpec(r.axes.dp))
            caches_sh = jax.tree_util.tree_map(
                lambda _: self._sharding(PartitionSpec(None, r.axes.dp)),
                caches)
            in_s = (r.params_sharding(params), caches_sh, rows, rows, rows,
                    rows)
            return in_s, (rows, caches_sh)
        caches_sh = jax.tree_util.tree_map(
            self._sharding, r.cache_spec(caches),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        in_s = (r.params_sharding(params), caches_sh, None, None, None, None)
        return in_s, (None, caches_sh)

    def decode_fn(self, params, caches):
        """Resolve the decode executable for this (slot count, cache
        geometry, params structure) once — cached per batch-slot count +
        KV scheme/page geometry + cache/params signature, so engines with
        different max_len/cache_dtype — or float vs paged-int8 caches —
        can share one runtime without colliding. The returned callable is
        the per-tick hot path: no signature hashing per token; its
        ``pages`` operand is the scheduler's page table (None for dense
        caches)."""
        if self.per_device and any(leaf.shape[1] % self._dp for leaf in
                                   jax.tree_util.tree_leaves(caches)):
            raise ValueError(
                f"per-device decode needs the slots and the page pool to "
                f"split evenly over the mesh's {self._dp} data shards")
        key = ("decode", self._plan_key, self._decode_batch(caches),
               T.kv_geometry(caches), _tree_sig(caches), _tree_sig(params))
        fn = self._get(key, self._build_decode,
                       shardings=None if self.rules is None else
                       (lambda: self._decode_shardings(params, caches)))

        def step(params, caches, tokens, pos, active, pages=None):
            self._stats["calls"] += 1
            args = (params, caches, jnp.asarray(tokens), jnp.asarray(pos),
                    jnp.asarray(active),
                    None if pages is None else jnp.asarray(pages))
            if key not in self._arg_shapes:
                self._note_args(key, args)
            return fn(*args)
        return step

    @staticmethod
    def _decode_batch(caches) -> int:
        """Slot count from the cache geometry (leaves are (steps, B, ...))."""
        return int(jax.tree_util.tree_leaves(caches)[0].shape[1])

    def decode(self, params, caches, tokens, pos, active, pages=None):
        """One decode step via a per-call key resolution — convenience for
        one-off callers; engines bind :meth:`decode_fn` instead."""
        return self.decode_fn(params, caches)(params, caches, tokens, pos,
                                              active, pages)
