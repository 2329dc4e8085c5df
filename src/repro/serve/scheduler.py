"""Scheduling layer shared by both serving engines.

Two admission disciplines over one queue abstraction:

* :class:`SlotScheduler` — the continuous-batching machinery extracted from
  the decode engine: a fixed number of batch *slots* (= the compiled batch
  size), FIFO admission into free slots, per-slot token cursors, immediate
  release on retirement. The engine owns model state (caches, sampling);
  the scheduler owns *which request runs where*.

* :class:`MicroBatcher` — dynamic micro-batching for encoder requests:
  per-length-bucket FIFO queues, flushed when a bucket reaches
  ``max_batch`` or its oldest request has waited ``max_wait`` seconds
  (latency bound), or on demand (drain). Requests of similar length batch
  together so padding waste stays bounded by the bucket geometry.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import numpy as np

from repro.serve.runtime import bucket_size


@dataclasses.dataclass
class EncoderRequest:
    """One encoder-workload request (classification / matching / tagging).

    ``tokens`` is the packed input ids (pairs arrive pre-packed as
    ``[CLS] a [SEP] b [SEP]`` with ``segments``); the engine fills
    ``logits`` / ``prediction`` at retirement.
    """
    uid: int
    tokens: list[int]
    segments: Optional[list[int]] = None
    # adaptive routing: the traffic-class tag the client sent (if any) and
    # the cluster id the router assigned at admission — requests only batch
    # with their own cluster, and the engine picks the cluster's plan
    traffic_class: Optional[str] = None
    cluster: int = 0
    # engine-filled:
    arrival: Optional[float] = None
    logits: Optional[np.ndarray] = None
    prediction: Optional[np.ndarray] = None
    done: bool = False


class PagePool:
    """Fixed pool of KV-cache pages with a per-slot page table.

    The table is the dense ``(slots, pages_per_slot)`` int32 array the
    decode executable takes as an operand: row ``s`` lists the page ids
    slot ``s`` owns in token order, ``-1`` beyond its allocation. Pages
    are handed out on demand (:meth:`ensure`) as a slot's sequence grows
    past a page boundary and returned wholesale on :meth:`release` —
    the paging analogue of vLLM's block allocator, sized so the pool can
    oversubscribe max-length worst cases when typical sequences are short.

    With ``shards`` > 1 the slots and the pages are split into that many
    equal, contiguous parts — one per device when each device of a
    data-parallel mesh holds its own part of the pool — and a slot only
    ever gets pages of its own part.
    """

    def __init__(self, num_pages: int, page_size: int, slots: int,
                 pages_per_slot: int, *, shards: int = 1):
        if slots % shards or num_pages % shards:
            raise ValueError(f"{slots} slots and {num_pages} pages do not "
                             f"split evenly into {shards} shards")
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.table = -np.ones((slots, pages_per_slot), np.int32)
        self._slots_per_shard = slots // shards
        per = num_pages // shards
        self.free: list[deque] = [deque(range(k * per, (k + 1) * per))
                                  for k in range(shards)]
        self.alloc_failures = 0

    def shard_of(self, s: int) -> int:
        """The pool shard slot ``s`` takes its pages from."""
        return s // self._slots_per_shard

    def ensure(self, s: int, tokens: int) -> bool:
        """Grow slot ``s`` to cover ``tokens`` total tokens. Returns False
        (table untouched) when the pool cannot supply enough pages — the
        caller must stall the slot until a release frees some."""
        need = -(-tokens // self.page_size) if tokens > 0 else 0
        if need > self.pages_per_slot:
            raise ValueError(f"slot {s} needs {need} pages > "
                             f"pages_per_slot={self.pages_per_slot}")
        have = int((self.table[s] >= 0).sum())
        free = self.free[self.shard_of(s)]
        if need - have > len(free):
            self.alloc_failures += 1
            return False
        for j in range(have, need):
            self.table[s, j] = free.popleft()
        return True

    def release(self, s: int) -> list[int]:
        """Free every page slot ``s`` owns; returns the freed ids (the
        engine invalidates their ``pages_pos`` rows so a reallocated page
        never leaks another request's positions)."""
        freed = [int(p) for p in self.table[s] if p >= 0]
        self.free[self.shard_of(s)].extend(freed)
        self.table[s] = -1
        return freed

    def pages_in_use(self) -> int:
        return self.num_pages - sum(len(f) for f in self.free)

    def bytes_per_page(self, caches) -> int:
        """Sum of one page's bytes across every paged leaf of ``caches``."""
        import jax
        total = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
            name = str(path[-1])
            if "pages_" in name:
                total += (leaf.size // leaf.shape[0]) * leaf.dtype.itemsize
        return total


class SlotScheduler:
    """Slot/admission/queue bookkeeping for token-level continuous batching.

    ``active[s]`` holds the request occupying slot ``s`` (None = free);
    ``cursor[s]`` counts the tokens that request has consumed (prompt then
    generated). The engine resets model state for slots returned by
    :meth:`admit` and calls :meth:`release` when a request retires.

    With a :class:`PagePool` attached the scheduler also owns the page
    lifecycle: release/cancel return the slot's pages to the pool and stash
    the freed ids in ``freed_pages`` for the engine to drain (it must reset
    those pages' position rows before the ids can be reused).
    """

    def __init__(self, slots: int, pool: Optional[PagePool] = None, *,
                 cluster_pure: bool = False):
        self.slots = slots
        self.queue: deque = deque()
        self.active: list = [None] * slots
        self.cursor = np.zeros(slots, np.int64)
        self.evicted = 0        # cancellations + deadline evictions
        self.pool = pool
        self.freed_pages: list[int] = []
        # adaptive routing: when True, admission keeps the live batch
        # cluster-pure — every tick runs ONE executable, so all active
        # slots must share one precision plan. Requests of other clusters
        # wait (FIFO among themselves) until the batch drains.
        self.cluster_pure = cluster_pure

    def submit(self, req) -> None:
        self.queue.append(req)

    @property
    def active_cluster(self) -> Optional[int]:
        """Cluster id of the live batch (None when no slot is occupied)."""
        for a in self.active:
            if a is not None:
                return getattr(a, "cluster", 0)
        return None

    def admit(self) -> list[int]:
        """Fill free slots FIFO; returns the newly-occupied slot ids (their
        per-slot state must be reset by the caller). In ``cluster_pure``
        mode only requests matching the live batch's cluster (or, on an
        empty batch, the queue head's cluster) are admitted; skipped
        requests keep their queue order."""
        newly = []
        if not self.cluster_pure:
            for s in range(self.slots):
                if self.active[s] is None and self.queue:
                    self.active[s] = self.queue.popleft()
                    self.cursor[s] = 0
                    newly.append(s)
            return newly
        free = [s for s in range(self.slots) if self.active[s] is None]
        if not free or not self.queue:
            return newly
        current = self.active_cluster
        if current is None:
            current = getattr(self.queue[0], "cluster", 0)
        skipped: deque = deque()
        while free and self.queue:
            req = self.queue.popleft()
            if getattr(req, "cluster", 0) == current:
                s = free.pop(0)
                self.active[s] = req
                self.cursor[s] = 0
                newly.append(s)
            else:
                skipped.append(req)
        skipped.extend(self.queue)
        self.queue = skipped
        return newly

    def live(self) -> list[int]:
        return [s for s in range(self.slots) if self.active[s] is not None]

    def release(self, s: int) -> None:
        self.active[s] = None
        if self.pool is not None:
            self.freed_pages.extend(self.pool.release(s))

    def cancel(self, req) -> Optional[str]:
        """Abandon ``req`` wherever it is: drop it from the admission queue
        (``"queued"``) or free its slot mid-generation (``"active"`` — the
        slot stops consuming batch occupancy immediately; its cache rows
        are reset on the next admit, exactly like a normal retirement).
        Returns None when the request is not held by this scheduler."""
        try:
            self.queue.remove(req)
            self.evicted += 1
            return "queued"
        except ValueError:
            pass
        for s in range(self.slots):
            if self.active[s] is req:
                self.release(s)
                self.evicted += 1
                return "active"
        return None

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(a is not None for a in self.active)


class MicroBatcher:
    """Per-(bucket, cluster) queues with size- and age-triggered flushing.

    ``submit`` files a request under ``(bucket_size(len(tokens)),
    req.cluster)`` — requests only batch with their own length bucket AND
    their own traffic cluster, so every micro-batch runs under exactly one
    precision plan (cluster-pure batches, see :mod:`repro.adaptive`).
    ``ready`` pops every batch that is due: a queue with >= ``max_batch``
    requests flushes a full batch, a queue whose head has waited
    >= ``max_wait`` flushes whatever is there, and ``force=True`` drains
    everything (shutdown / synchronous callers).

    The max-wait drain pass visits *every* queue on every call and flushes
    each overdue one — a quiet cluster's partial batch can never be
    stranded behind a busy sibling queue that keeps hitting the
    ``max_batch`` trigger (``tests/test_adaptive.py`` pins this).
    """

    def __init__(self, *, max_batch: int = 8, max_wait: float = 0.0,
                 min_len: int = 8, max_len: Optional[int] = None):
        self.max_batch = max_batch
        self.max_wait = max_wait
        self.min_len = min_len
        self.max_len = max_len
        self._queues: dict[tuple[int, int], deque] = {}
        self.evicted = 0        # cancellations + deadline evictions

    def bucket(self, length: int) -> int:
        return bucket_size(length, self.min_len, self.max_len)

    def submit(self, req: EncoderRequest, now: Optional[float] = None) -> int:
        """File ``req``; returns the length bucket it landed in."""
        b = self.bucket(len(req.tokens))
        req.arrival = time.monotonic() if now is None else now
        key = (b, getattr(req, "cluster", 0))
        self._queues.setdefault(key, deque()).append(req)
        return b

    def ready(self, now: Optional[float] = None,
              force: bool = False) -> list[tuple[int, list[EncoderRequest]]]:
        """Pop and return every due batch as (length_bucket, requests);
        each returned batch is cluster-pure (read ``reqs[0].cluster``)."""
        now = time.monotonic() if now is None else now
        out = []
        # every queue gets its own independent due-check: iterating a
        # snapshot of ALL keys (not stopping at the first due one) is what
        # guarantees overdue partial buckets all flush in this one tick
        for key in sorted(self._queues):
            q = self._queues[key]
            while q and (force or len(q) >= self.max_batch
                         or now - q[0].arrival >= self.max_wait):
                out.append((key[0], [q.popleft()
                                     for _ in range(min(self.max_batch,
                                                        len(q)))]))
        return out

    def depth_by_cluster(self) -> dict[int, int]:
        """Queued request count per cluster id (metrics surface)."""
        out: dict[int, int] = {}
        for (_b, c), q in self._queues.items():
            out[c] = out.get(c, 0) + len(q)
        return out

    def evict(self, predicate) -> list[EncoderRequest]:
        """Remove every queued request with ``predicate(req)`` true —
        deadline expiry and client disconnects — BEFORE it is batched, so
        abandoned work never occupies a micro-batch row. Arrival order of
        the survivors is preserved. Returns the evicted requests."""
        out: list[EncoderRequest] = []
        for blen, q in self._queues.items():
            keep: deque = deque()
            for req in q:
                (out if predicate(req) else keep).append(req)
            self._queues[blen] = keep
        self.evicted += len(out)
        return out

    def cancel(self, req: EncoderRequest) -> bool:
        """Drop one queued request (no-op if already flushed)."""
        return bool(self.evict(lambda r: r is req))

    def __len__(self) -> int:
        return sum(len(q) for q in self._queues.values())
