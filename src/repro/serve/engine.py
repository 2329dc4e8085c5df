"""Token-level continuous-batching engine over the SAMP-quantized model.

The decode half of the serving stack, rebuilt on the shared layers:

* scheduling — a :class:`~repro.serve.scheduler.SlotScheduler`: a fixed
  number of batch *slots* (= the compiled batch size), FIFO admission,
  per-slot token cursors, immediate slot release on retirement;
* execution — a :class:`~repro.serve.runtime.Runtime`: the jitted decode
  step is cached per (plan, scheme, slot-count) bucket, shared with any
  other engine or benchmark bound to the same runtime.

Scheduling model (token-level continuous batching): every tick runs ONE
compiled decode step for the whole batch with per-slot positions; each
active slot consumes one token — its next *prompt* token while prefilling,
or its last *generated* token while decoding — so new requests stream in
token-by-token alongside in-flight generations, no wave barriers. Idle
slots are masked via ``active`` — the model gates their cache/state writes,
so they are never corrupted and never retraced. Finished requests free
their slot immediately; the slot's cache rows are reset on the next admit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.serve.runtime import Runtime
from repro.serve.scheduler import PagePool, SlotScheduler

# page geometry when a plan implies paging but the caller picked no size
DEFAULT_PAGE_SIZE = 16


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    # adaptive routing (see repro.adaptive): tag from the client, cluster
    # id assigned at admission — decode batches stay cluster-pure
    traffic_class: Optional[str] = None
    cluster: int = 0
    # engine-filled:
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def text_len(self) -> int:
        return len(self.prompt) + len(self.output)


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, plan, *,
                 scheme: T.QuantScheme = T.QuantScheme(),
                 batch_slots: int = 4, max_len: int = 256,
                 cache_dtype=jnp.float32, compute_dtype=jnp.float32,
                 seed: int = 0, runtime: Optional[Runtime] = None,
                 backend="reference", mesh=None,
                 page_size: Optional[int] = None,
                 kv_cache: Optional[str] = None,
                 pool_pages: Optional[int] = None,
                 precision=None, router=None):
        # ``backend`` names the compute backend (repro.kernels.backend) the
        # engine's Runtime executes on, ``mesh`` the serving mesh it places
        # executables over; both are ignored when a runtime is passed in
        # (the shared runtime's backend/mesh govern).
        #
        # ``page_size`` switches the KV caches to the paged layout (pages
        # allocated on demand, freed on retirement/cancel — see
        # repro.models.layers). ``kv_cache`` picks the page scheme for every
        # full-attention layer ("float" / "int8_per_head" /
        # "int8_per_token"); None takes per-layer schemes from ``precision``
        # (a PrecisionPlan) when given, else float. ``pool_pages`` sizes the
        # shared page pool (default: no oversubscription —
        # slots * pages_per_slot).
        # ``router`` (a repro.adaptive.PlanRouter) makes decode serving
        # input-adaptive: admission stamps each request's cluster, the slot
        # scheduler keeps the live batch cluster-pure, and every tick runs
        # the active cluster's (params, plan) executable. The KV-cache tree
        # is SHARED across clusters (slots outlive cluster switches), so a
        # routed decode deployment requires uniform kv_schemes across the
        # PlanSet members.
        if not cfg.supports_decode:
            raise ValueError(f"{cfg.name} is encoder-only; no decode — "
                             f"serve it through EncoderServeEngine")
        if router is not None:
            if not router.uniform_kv():
                raise ValueError(
                    "routed decode shares one KV-cache tree across "
                    "clusters: every PlanSet member must name the same "
                    "per-layer kv_cache schemes")
            if precision is None:
                precision = router.planset.plan_for(router.planset.default)
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.scheme = scheme
        self.slots = batch_slots
        self.max_len = max_len
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype
        if page_size is None and kv_cache is None and precision is not None \
                and getattr(precision, "num_quant_kv", 0):
            # the plan itself asks for quantized KV: paging is implied
            page_size = DEFAULT_PAGE_SIZE
        self.page_size = page_size
        self.runtime = runtime or Runtime(cfg, plan, scheme=scheme,
                                          precision=precision,
                                          compute_dtype=compute_dtype,
                                          backend=backend, mesh=mesh)
        self.pool: Optional[PagePool] = None
        cache_kw = {}
        if page_size is not None:
            if kv_cache is not None:
                schemes = (kv_cache,) * cfg.num_layers
            elif precision is not None:
                schemes = precision.kv_schemes
            else:
                schemes = ("float",) * cfg.num_layers
            pps = T.pages_per_slot(max_len, page_size)
            num_pages = (pool_pages if pool_pages is not None
                         else batch_slots * pps)
            # a runtime that decodes per device holds each device's share
            # of the pool there, so the pool keeps each slot's pages on the
            # slot's own device
            self.pool = PagePool(num_pages, page_size, batch_slots, pps,
                                 shards=self.runtime.shards)
            cache_kw = dict(page_size=page_size, num_pages=num_pages,
                            kv_schemes=schemes)
        elif kv_cache not in (None, "float"):
            raise ValueError("kv_cache quantization needs the paged layout; "
                             "pass page_size= as well")
        self.sched = SlotScheduler(batch_slots, pool=self.pool,
                                   cluster_pure=router is not None)
        self.router = router
        if router is not None and not router.bound:
            router.bind(self.runtime)
        self.caches = T.init_caches(cfg, plan, batch_slots, max_len,
                                    cache_dtype, **cache_kw)
        self._fresh1 = T.init_caches(cfg, plan, 1, max_len, cache_dtype,
                                     **{**cache_kw, "num_pages": 1}
                                     if cache_kw else {})
        # resolve executables once; ticks pay no key-hashing cost. Routed
        # engines resolve lazily per cluster (each sibling caches its own
        # executable under its (fingerprint, cluster) key).
        self._decode = (None if router is not None
                        else self.runtime.decode_fn(params, self.caches))
        self._decode_by_cluster: dict[int, object] = {}
        self.rng = np.random.default_rng(seed)
        self._stats = {"ticks": 0, "tokens": 0, "retired": 0, "stalls": 0,
                       "preemptions": 0, "requests": 0}
        # set when a deadlock preemption proves the pool cannot hold the
        # current working set: admission pauses until pages are freed, so
        # preempted requests don't thrash straight back into a slot
        self._admission_hold = False
        self._reset_fn = None               # built lazily on first admit
        self._inval_fn = None               # built lazily on first drain

    # back-compat views onto the extracted scheduler
    @property
    def queue(self):
        return self.sched.queue

    @property
    def active(self):
        return self.sched.active

    # -- request lifecycle ------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        if len(req.prompt) + req.max_tokens > self.max_len:
            raise ValueError(f"prompt+max_tokens exceeds max_len "
                             f"{self.max_len}")
        if self.router is not None:
            self.router.admit(req)      # stamps req.cluster before queueing
        self.sched.submit(req)
        self._stats["requests"] += 1

    def _reset_slot(self, s: int) -> None:
        """Zero slot s's cache rows (leaves carry batch on axis 1, after the
        layer-stack axis). Paged pool leaves have no batch axis — their
        per-slot state is the page table, owned by the scheduler; stale
        page contents are invalidated via :meth:`_drain_freed`. One jitted
        update for the whole tree, slot index as an operand: admits cost a
        single dispatch, not a scatter per cache leaf."""
        if self._reset_fn is None:
            def reset_tree(caches, fresh, at):
                def reset(path, old, fr):
                    if "pages_" in str(path[-1]):
                        return old
                    return jax.lax.dynamic_update_slice_in_dim(
                        old, fr.astype(old.dtype), at, axis=1)
                return jax.tree_util.tree_map_with_path(reset, caches, fresh)
            # donation: the old cache buffers are dead after the update,
            # so XLA updates in place instead of copying the whole tree
            self._reset_fn = jax.jit(reset_tree, donate_argnums=(0,))
        self.caches = self._reset_fn(self.caches, self._fresh1,
                                     jnp.int32(s))

    def _drain_freed(self) -> None:
        """Invalidate the position rows of pages the scheduler freed since
        the last tick, BEFORE their ids can be reallocated — a reused page
        must never expose another request's positions to band_mask."""
        freed = self.sched.freed_pages
        if not freed:
            return
        self.sched.freed_pages = []
        self._admission_hold = False        # headroom again: admit freely
        # fixed-shape index vector (padded with an out-of-range id that
        # mode="drop" discards): a varying-length idx would recompile the
        # scatter once per distinct freed-page count and dominate the tick
        npages = self.pool.num_pages
        uniq = sorted(set(freed))
        pad = np.full((npages,), npages, np.int32)
        pad[:len(uniq)] = uniq
        if self._inval_fn is None:
            def inval_tree(caches, idx):
                def inval(path, leaf):
                    if "pages_pos" in str(path[-1]):
                        return leaf.at[:, idx].set(-1, mode="drop")
                    return leaf
                return jax.tree_util.tree_map_with_path(inval, caches)
            self._inval_fn = jax.jit(inval_tree, donate_argnums=(0,))
        self.caches = self._inval_fn(self.caches, jnp.asarray(pad))

    # -- the serving loop ---------------------------------------------------------
    def step(self) -> list[Request]:
        """One engine tick = one compiled decode step for the whole batch."""
        if not self._admission_hold:
            for s in self.sched.admit():
                self._reset_slot(s)
        self._drain_freed()
        live = self.sched.live()
        if not live:
            return []
        if self.pool is not None:
            # grow each live slot's page allocation to cover this tick's
            # token; slots the pool cannot serve stall (masked inactive,
            # cursor not advanced) until a retirement frees pages
            need = lambda s: int(self.sched.cursor[s]) + 1
            stalled = [s for s in live if not self.pool.ensure(s, need(s))]
            if stalled:
                self._stats["stalls"] += len(stalled)
                shard = self.pool.shard_of
                stuck = [s for s in stalled
                         if all(t in stalled for t in live
                                if shard(t) == shard(s))]
                if stuck:
                    # deadlock: every live slot of a pool shard (the whole
                    # pool, unless it is split per device) needs a page and
                    # none can retire to free one. Preempt the shard's
                    # youngest slot (least progress lost): its request goes
                    # back to the queue head — replayed from its prompt on
                    # re-admission — and its freed pages unblock the others.
                    group = [s for s in stuck if shard(s) == shard(stuck[0])]
                    if len(group) == 1:
                        raise RuntimeError(
                            "page pool exhausted: a single request needs "
                            "more pages than the pool holds; raise "
                            "pool_pages")
                    victim = min(group,
                                 key=lambda s: int(self.sched.cursor[s]))
                    req = self.sched.active[victim]
                    self.sched.release(victim)
                    self.sched.queue.appendleft(req)
                    self._drain_freed()
                    self._admission_hold = True
                    self._stats["preemptions"] += 1
                    live.remove(victim)
                    stalled = [s for s in live
                               if not self.pool.ensure(s, need(s))]
                live = [s for s in live if s not in stalled]
                if not live:
                    return []
        tokens = np.zeros((self.slots, 1), np.int32)
        pos = np.zeros(self.slots, np.int32)
        active = np.zeros(self.slots, bool)
        for s in live:
            req = self.sched.active[s]
            c = int(self.sched.cursor[s])
            # prompt, then generated tokens: at steady state this is
            # output[-1]; after a page-pool preemption it replays the
            # already-generated prefix before sampling resumes
            tokens[s, 0] = (req.prompt[c] if c < len(req.prompt)
                            else req.output[c - len(req.prompt)])
            pos[s] = c
            active[s] = True
        pages = (jnp.asarray(self.pool.table) if self.pool is not None
                 else None)
        if self.router is not None:
            # cluster-pure batch: the scheduler guarantees every live slot
            # shares one cluster — run that cluster's executable + params
            entry = self.router.entry(self.sched.active_cluster)
            decode = self._decode_by_cluster.get(entry.cluster)
            if decode is None:
                decode = entry.runtime.decode_fn(entry.params, self.caches)
                self._decode_by_cluster[entry.cluster] = decode
            step_params = entry.params
        else:
            decode, step_params = self._decode, self.params
        logits, self.caches = decode(
            step_params, self.caches, tokens, pos, active, pages)
        logits = np.asarray(jax.device_get(logits), np.float32)
        self._stats["ticks"] += 1
        self._stats["tokens"] += len(live)

        retired: list[Request] = []
        for s in live:
            req = self.sched.active[s]
            self.sched.cursor[s] += 1
            # still consuming the prompt (or replaying generated tokens
            # after a preemption)? sampling resumes at the text frontier
            if self.sched.cursor[s] < req.text_len:
                continue
            # this tick's logits predict the next token
            row = logits[s]
            if req.temperature > 0:
                p = np.exp((row - row.max()) / req.temperature)
                p /= p.sum()
                nxt = int(self.rng.choice(len(p), p=p))
            else:
                nxt = int(row.argmax())
            req.output.append(nxt)
            hit_eos = req.eos_id is not None and nxt == req.eos_id
            if hit_eos or len(req.output) >= req.max_tokens \
                    or req.text_len >= self.max_len:
                req.done = True
                retired.append(req)
                self.sched.release(s)
                self._stats["retired"] += 1
        return retired

    def run(self, max_ticks: int = 100_000) -> list[Request]:
        """Drain queue + in-flight work; returns requests in retire order."""
        done: list[Request] = []
        ticks = 0
        while self.sched.busy and ticks < max_ticks:
            done.extend(self.step())
            ticks += 1
        return done

    @property
    def kv_cache_bytes(self) -> int:
        """Total decode-cache footprint (all leaves, paged or dense) — the
        ``samp_kv_cache_bytes`` gauge."""
        return T.cache_bytes(self.caches)

    @property
    def kv_pages_in_use(self) -> int:
        """Allocated pages in the pool (0 for dense caches) — the
        ``samp_kv_pages_in_use`` gauge."""
        return self.pool.pages_in_use() if self.pool is not None else 0

    @property
    def stats(self) -> dict:
        # the unified counters surface (queue depth / occupancy /
        # completed / evicted) comes from serve.metrics.engine_counters —
        # the same numbers the /metrics endpoint exports
        from repro.serve.metrics import engine_counters
        s = dict(self._stats)
        s.update({f"runtime_{k}": v for k, v in self.runtime.stats.items()
                  if k != "buckets"})
        s.update(engine_counters(self))
        return s
