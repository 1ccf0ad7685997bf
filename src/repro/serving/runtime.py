"""ServingRuntime: concurrent queries, upserts, deletes, and compaction
over one (P)DET-LSH index (docs/DESIGN.md §9).

The runtime composes three orthogonal pieces:

  * **Epoch pinning (RCU)** — every query batch pins an immutable epoch of
    the index (``StreamingDETLSH.pin_state()`` + a manifest refcount).
    Mutators install the next epoch atomically (manifest swap / memtable
    version bump) and an old epoch retires only when its reader count
    drains, so readers never block writers, writers never invalidate
    in-flight readers, and no reader can observe a half-swapped manifest.
  * **Deadline-aware micro-batching** — ``scheduler.MicroBatcher`` decides
    when a batch flushes and which requests are admitted / served degraded
    (capped ``max_rounds``) / shed with an explicit ``Rejected``.
  * **Fault injection + retry** — a ``faults.FaultPlan`` fires at the
    engine-call and compaction-swap boundaries.  A failed engine call is
    retried once on the vmap semantics-of-record engine; a second failure
    rejects only that batch's requests.  A compaction that crashes at the
    swap leaves the manifest — and every pinned epoch — untouched.

Serialized-oracle equivalence (the §9 correctness argument): mutations are
*barriers* — ``upsert``/``delete`` flush the queue before touching the
index — and every batch answers on the epoch it pinned, so the sequence of
answers is bit-identical to running each operation to completion in
submission order.  Compaction is *not* a barrier: it only reorganizes the
surviving set, and pinned epochs keep answering on pre-compaction
structure, which is exactly what the property test checks
(tests/test_runtime_properties.py).

Metrics are lock-free on the read path: latencies and queue waits land
in bounded ``LatencyRing``s (fixed numpy buffer, monotonic write index)
and counters are plain ints — single-writer in this in-process model, and
safe to read at any time without coordination.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.api.protocol import LegacyIndexAdapter, MutableAnnIndex, \
    as_ann_index
from repro.api.request import SearchRequest
from repro.serving import faults as flt
from repro.serving.scheduler import Answer, LatencyModel, MicroBatcher, \
    Rejected, Request

Outcome = Union[Answer, Rejected]


class LatencyRing:
    """Bounded latency buffer: fixed numpy storage, monotonic write index.

    Drop-in for the old unbounded ``latencies_ms`` list on the metrics
    path — ``append``/``len``/iteration/``np.percentile`` all behave like
    a list of the most recent ``capacity`` samples, but memory is O(1) for
    the lifetime of the service.  ``total`` counts every sample ever
    recorded (``len`` saturates at capacity).
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._buf = np.zeros(capacity, np.float64)
        self.total = 0

    def append(self, value: float) -> None:
        self._buf[self.total % self.capacity] = value
        self.total += 1

    def __len__(self) -> int:
        return min(self.total, self.capacity)

    def values(self) -> np.ndarray:
        """Retained samples, oldest first."""
        n = len(self)
        if self.total <= self.capacity:
            return self._buf[:n].copy()
        split = self.total % self.capacity
        return np.concatenate([self._buf[split:], self._buf[:split]])

    def __iter__(self):
        return iter(self.values())

    def __array__(self, dtype=None, copy=None):
        vals = self.values()
        return vals.astype(dtype) if dtype is not None else vals

    def percentile(self, p: float) -> float:
        if len(self) == 0:
            return float("nan")
        return float(np.percentile(self.values(), p))


@dataclasses.dataclass
class RuntimeStats:
    """Counters + bounded latency ring; everything lands in ``summary()``."""

    latencies: LatencyRing = dataclasses.field(
        default_factory=lambda: LatencyRing(4096))
    # per answered query: arrival -> its batch's start (Answer.queue_ms)
    queue_waits: LatencyRing = dataclasses.field(
        default_factory=lambda: LatencyRing(4096))
    queries: int = 0            # real served queries — never pad lanes
    batches: int = 0
    pad_queries: int = 0
    degraded_batches: int = 0
    upserts: int = 0
    deletes: int = 0
    noop_deletes: int = 0       # delete() of never-inserted gids
    compactions: int = 0
    compaction_crashes: int = 0
    retries: int = 0            # engine-call retries on the vmap engine
    deadline_misses: int = 0    # answered, but past the stated deadline
    epochs_pinned: int = 0
    epochs_retired: int = 0
    max_queue_depth: int = 0
    # durability counters (docs/DESIGN.md §13) — zero unless the served
    # index is a durability.DurableIndex; mirrored from its WAL
    wal_bytes: int = 0
    fsyncs: int = 0
    checkpoints: int = 0
    checkpoint_failures: int = 0
    recovery_replayed: int = 0  # WAL records replayed by recovery-on-start
    shed: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"deadline": 0, "queue_full": 0,
                                 "engine_failure": 0})

    def record_shed(self, rejected: Rejected) -> None:
        self.shed[rejected.reason] = self.shed.get(rejected.reason, 0) + 1

    @property
    def shed_total(self) -> int:
        return sum(self.shed.values())

    def percentile(self, p: float) -> float:
        return self.latencies.percentile(p)

    def summary(self) -> dict:
        return {
            "queries": self.queries, "batches": self.batches,
            "pad_queries": self.pad_queries,
            "degraded_batches": self.degraded_batches,
            "upserts": self.upserts, "deletes": self.deletes,
            "noop_deletes": self.noop_deletes,
            "compactions": self.compactions,
            "compaction_crashes": self.compaction_crashes,
            "retries": self.retries,
            "deadline_misses": self.deadline_misses,
            "shed": dict(self.shed), "shed_total": self.shed_total,
            "epochs_pinned": self.epochs_pinned,
            "epochs_retired": self.epochs_retired,
            "max_queue_depth": self.max_queue_depth,
            "wal_bytes": self.wal_bytes, "fsyncs": self.fsyncs,
            "checkpoints": self.checkpoints,
            "checkpoint_failures": self.checkpoint_failures,
            "recovery_replayed": self.recovery_replayed,
            "p50_ms": self.percentile(50.0),
            "p99_ms": self.percentile(99.0),
            "p999_ms": self.percentile(99.9),
            "queue_p50_ms": self.queue_waits.percentile(50.0),
            "queue_p99_ms": self.queue_waits.percentile(99.0),
        }


class Epoch:
    """One pinned, immutable read view.  Created by ``EpochManager.pin``;
    must be released exactly once (the runtime does so in a finally)."""

    def __init__(self, epoch_id: int, index, view, token: Optional[int]):
        self.epoch_id = epoch_id
        self._index = index
        self.view = view                 # streaming PinnedView, or None
        self._token = token              # manifest.retain() version token
        self.released = False

    @property
    def fingerprint(self) -> Optional[tuple]:
        return self.view.fingerprint if self.view is not None else None

    def search(self, queries, request: SearchRequest):
        """Answer on the pinned structure, regardless of mutations since."""
        if self.view is not None:
            return self._index.search(queries, request, view=self.view)
        return self._index.search(queries, request)


class EpochManager:
    """Epoch lifecycle: pin / release / advance, with retire-on-drain.

    For a ``StreamingDETLSH`` each pin captures a fresh ``pin_state()``
    view (fresh because sealed-row deletes mutate host bitmaps without
    bumping a version — a cached view could silently go stale) and takes a
    manifest refcount, so ``manifest.pinned_versions()`` makes the drain
    state observable.  Immutable indexes (static DET-LSH, sharded PDET)
    get trivial epochs: every state they will ever have *is* an immutable
    snapshot.
    """

    def __init__(self, index, stats: RuntimeStats):
        self._index = index
        self._stats = stats
        self._streaming = hasattr(index, "pin_state")
        self.current_id = 0
        self._readers: Dict[int, int] = {}   # epoch_id -> outstanding pins

    def pin(self) -> Epoch:
        if self._streaming:
            view = self._index.pin_state()
            token = self._index.manifest.retain()
        else:
            view, token = None, None
        eid = self.current_id
        self._readers[eid] = self._readers.get(eid, 0) + 1
        self._stats.epochs_pinned += 1
        return Epoch(eid, self._index, view, token)

    def release(self, epoch: Epoch) -> None:
        if epoch.released:
            raise ValueError(f"epoch {epoch.epoch_id} released twice")
        epoch.released = True
        if epoch._token is not None:
            self._index.manifest.release(epoch._token)
        eid = epoch.epoch_id
        remaining = self._readers.get(eid, 0) - 1
        if remaining > 0:
            self._readers[eid] = remaining
            return
        self._readers.pop(eid, None)
        if eid != self.current_id:
            self._stats.epochs_retired += 1   # superseded + drained

    def advance(self) -> int:
        """Install the next epoch (called by mutators after success).  The
        superseded epoch retires immediately if it has no readers."""
        old = self.current_id
        self.current_id += 1
        if old not in self._readers:
            pass                              # never pinned — nothing drains
        return self.current_id

    def outstanding(self) -> Dict[int, int]:
        return dict(self._readers)


class ServingRuntime:
    """Deadline-aware, epoch-pinned, fault-tolerant serving loop.

    In-process model of the production service: ``submit`` enqueues,
    ``pump`` flushes batches the scheduler says are ready, ``flush``
    drains.  Mutations (``upsert``/``delete``) are barriers; ``compact``
    is not (pinned epochs survive it).  All answers and rejections are
    explicit ``Answer``/``Rejected`` outcomes keyed by request id.
    """

    def __init__(self, index, k: int = 10, *, max_batch: int = 32,
                 pad_to: int = 32, max_wait_ms: float = 2.0,
                 deadline_headroom: float = 1.0,
                 degraded_max_rounds: int = 8,
                 queue_cap: Optional[int] = None,
                 fault_plan: Optional[flt.FaultPlan] = None,
                 clock=time.perf_counter,
                 request: Optional[SearchRequest] = None,
                 latency_ring_capacity: int = 4096):
        self.index = index
        self._index = as_ann_index(index)
        self.k = k
        self.clock = clock
        self.degraded_max_rounds = degraded_max_rounds
        self.plan = fault_plan or flt.FaultPlan()
        self.stats = RuntimeStats(
            latencies=LatencyRing(latency_ring_capacity),
            queue_waits=LatencyRing(latency_ring_capacity))
        self.batcher = MicroBatcher(
            max_batch=max_batch, pad_to=pad_to, max_wait=max_wait_ms / 1e3,
            deadline_headroom=deadline_headroom, queue_cap=queue_cap,
            latency_model=LatencyModel())
        self.epochs = EpochManager(self._index, self.stats)
        # template request: k/n_active/max_rounds are runtime-controlled
        self._request = request or SearchRequest()
        if self._request.k != k:
            self._request = dataclasses.replace(self._request, k=k)
        self._rid = 0
        self.outcomes: Dict[int, Outcome] = {}
        # compaction-swap fault boundary: the manifest fires the plan
        # before mutating, so an armed fault models a mid-install crash
        if hasattr(self._index, "manifest"):
            self._index.manifest.swap_hook = \
                lambda: self.plan.fire(flt.COMPACTION_SWAP)
        self.last_compaction_error: Optional[BaseException] = None
        self.last_checkpoint_error: Optional[BaseException] = None
        # recovery-on-start: a recovered DurableIndex carries its report
        recovery = getattr(self._index, "last_recovery", None)
        if recovery is not None:
            self.stats.recovery_replayed = recovery.n_replayed
        self._sync_durability_stats()

    # ------------------------------------------------------------------
    # Query path
    # ------------------------------------------------------------------

    def submit(self, query, deadline: Optional[float] = None,
               arrival: Optional[float] = None) -> int:
        """Enqueue one query; returns its request id.  The outcome
        (``Answer`` or ``Rejected``) appears in ``self.outcomes[rid]``
        once a ``pump``/``flush`` runs its batch — a queue-full rejection
        appears immediately."""
        rid = self._rid
        self._rid += 1
        req = Request(rid=rid, query=np.asarray(query, np.float32),
                      arrival=self.clock() if arrival is None else arrival,
                      deadline=deadline)
        rejected = self.batcher.enqueue(req)
        if rejected is not None:
            self.outcomes[rid] = rejected
            self.stats.record_shed(rejected)
        else:
            self.stats.max_queue_depth = max(self.stats.max_queue_depth,
                                             self.batcher.depth)
        return rid

    def pump(self) -> int:
        """Run every batch the scheduler considers ready; returns how many
        batches ran."""
        ran = 0
        while self.batcher.ready(self.clock()):
            self._run_batch()
            ran += 1
        return ran

    def flush(self) -> int:
        """Drain the queue completely (mutation barrier / shutdown)."""
        ran = 0
        while len(self.batcher):
            self._run_batch()
            ran += 1
        return ran

    def _make_request(self, n_valid: int, degraded: bool) -> SearchRequest:
        req = dataclasses.replace(self._request, n_active=n_valid)
        if degraded:
            req = dataclasses.replace(
                req, max_rounds=min(req.max_rounds, self.degraded_max_rounds))
        return req

    def _run_batch(self) -> None:
        with tracing.span("detlsh.serve.batch",
                          batch_id=self.stats.batches) as sp:
            now = self.clock()
            batch, degraded, shed = self.batcher.next_batch(now)
            for rej in shed:
                self.outcomes[rej.rid] = rej
                self.stats.record_shed(rej)
            if not batch:
                return

            qs = np.stack([r.query for r in batch])
            pad = self.batcher.bucket(len(qs)) - len(qs)
            if pad:
                qs = np.concatenate([qs, np.zeros((pad, qs.shape[1]),
                                                  qs.dtype)])
            bucket = qs.shape[0]
            req = self._make_request(len(batch), degraded)
            waits_ms = [(now - r.arrival) * 1e3 for r in batch]
            sp.set(queries=len(batch), pad=pad, bucket=bucket,
                   degraded=degraded, wait_ms_sum=sum(waits_ms),
                   wait_ms_max=max(waits_ms))

            epoch = self.epochs.pin()
            try:
                t0 = self.clock()
                try:
                    self.plan.fire(flt.ENGINE_CALL)
                    res = epoch.search(jnp.asarray(qs), req)
                    jax.block_until_ready(res.dists)
                except Exception as first:
                    # retry once on the vmap semantics-of-record engine; a
                    # second failure rejects only this batch's requests
                    self.stats.retries += 1
                    retry_req = dataclasses.replace(req, engine="vmap")
                    try:
                        self.plan.fire(flt.ENGINE_CALL)
                        res = epoch.search(jnp.asarray(qs), retry_req)
                        jax.block_until_ready(res.dists)
                    except Exception as second:
                        for r in batch:
                            rej = Rejected(
                                r.rid, "engine_failure",
                                f"engine call failed twice: {first!r}; "
                                f"retry on vmap: {second!r}")
                            self.outcomes[r.rid] = rej
                            self.stats.record_shed(rej)
                        self.stats.batches += 1
                        return
                done = self.clock()
            finally:
                self.epochs.release(epoch)

            self.batcher.model.observe(bucket, degraded, max(0.0, done - t0))
            ids = np.asarray(res.ids)
            dists = np.asarray(res.dists)
            for i, (r, wait_ms) in enumerate(zip(batch, waits_ms)):
                latency_ms = (done - r.arrival) * 1e3
                self.stats.latencies.append(latency_ms)
                self.stats.queue_waits.append(wait_ms)
                if r.deadline is not None and done > r.deadline:
                    self.stats.deadline_misses += 1
                self.outcomes[r.rid] = Answer(
                    rid=r.rid, ids=ids[i], dists=dists[i],
                    epoch=epoch.epoch_id, degraded=degraded,
                    latency_ms=latency_ms, engine=res.stats.engine,
                    queue_ms=wait_ms)
            self.stats.batches += 1
            self.stats.queries += len(batch)
            self.stats.pad_queries += pad
            if degraded:
                self.stats.degraded_batches += 1

    def serve(self, request_stream) -> List[Outcome]:
        """Closed-loop convenience: feed ``(arrival, vec)`` or ``(arrival,
        vec, deadline)`` tuples, pump as they arrive, drain, and return the
        outcomes in submission order."""
        rids = []
        for item in request_stream:
            arrival, vec = item[0], item[1]
            deadline = item[2] if len(item) > 2 else None
            rids.append(self.submit(vec, deadline=deadline, arrival=arrival))
            self.pump()
        self.flush()
        return [self.outcomes.pop(rid) for rid in rids]

    # ------------------------------------------------------------------
    # Epoch surface (tests pin across mutations)
    # ------------------------------------------------------------------

    def pin(self) -> Epoch:
        return self.epochs.pin()

    def release(self, epoch: Epoch) -> None:
        self.epochs.release(epoch)

    # ------------------------------------------------------------------
    # Mutation path (barriers — docs/DESIGN.md §9 oracle argument)
    # ------------------------------------------------------------------

    def _mutable_index(self):
        if not isinstance(self._index, MutableAnnIndex):
            raise TypeError(
                f"{type(self.index).__name__} is immutable — serve a "
                f"streaming.StreamingDETLSH for upsert/delete")
        return self._index

    def upsert(self, vectors, gids=None) -> np.ndarray:
        """Flush queued queries (mutation barrier), then insert/overwrite.
        A validation failure (gid exhaustion) raises *after* the flush and
        *before* any index mutation, so no queued request is ever lost —
        recover with ``index.grow_id_capacity`` and resubmit the upsert."""
        idx = self._mutable_index()
        self.flush()
        out = idx.upsert(vectors, gids)
        self.stats.upserts += len(out)
        self.epochs.advance()
        if self._maybe_compact():
            self.stats.compactions += 1
        self._maybe_checkpoint()
        return out

    def delete(self, gids) -> int:
        """Flush, then tombstone; never-inserted gids are a counted no-op
        (``stats.noop_deletes``), not an error."""
        idx = self._mutable_index()
        self.flush()
        requested = int(np.atleast_1d(np.asarray(gids)).size)
        removed = idx.delete(gids)
        self.stats.deletes += removed
        self.stats.noop_deletes += requested - removed
        self.epochs.advance()
        if self._maybe_compact():
            self.stats.compactions += 1
        self._maybe_checkpoint()
        return removed

    def compact(self, force: bool = True) -> bool:
        """Run compaction concurrently with pinned epochs (NOT a barrier:
        merging the surviving set changes no answer, and pinned epochs keep
        answering on the pre-compaction structure).  A crash at the swap
        boundary leaves the manifest on the pre-swap epoch; the runtime
        records it and keeps serving."""
        idx = self._mutable_index()
        try:
            did = idx.compact() if force else idx.maybe_compact()
        except Exception as exc:
            self.stats.compaction_crashes += 1
            self.last_compaction_error = exc
            return False
        if did:
            self.stats.compactions += 1
            self.epochs.advance()
        return did

    def _maybe_compact(self) -> bool:
        try:
            did = self._index.maybe_compact()
        except Exception as exc:
            self.stats.compaction_crashes += 1
            self.last_compaction_error = exc
            return False
        if did:
            self.epochs.advance()
        return did

    # ------------------------------------------------------------------
    # Durability (docs/DESIGN.md §13) — active when the served index is a
    # durability.DurableIndex; a no-op otherwise
    # ------------------------------------------------------------------

    def _sync_durability_stats(self) -> None:
        wal = getattr(self._index, "wal", None)
        if wal is not None:
            self.stats.wal_bytes = wal.appended_bytes
            self.stats.fsyncs = wal.fsyncs

    def _maybe_checkpoint(self) -> bool:
        """Background checkpoint policy: let the index decide (WAL bytes /
        age thresholds).  A checkpoint failure is recorded and served
        around, like a compaction crash — the WAL still has every op, so
        durability degrades to a longer replay, not data loss."""
        mc = getattr(self._index, "maybe_checkpoint", None)
        if mc is None:
            return False
        try:
            did = bool(mc())
        except Exception as exc:
            self.stats.checkpoint_failures += 1
            self.last_checkpoint_error = exc
            self._sync_durability_stats()
            return False
        if did:
            self.stats.checkpoints += 1
        self._sync_durability_stats()
        return did

    # ------------------------------------------------------------------
    # Warmup
    # ------------------------------------------------------------------

    def warmup(self, d: int) -> None:
        """Compile every pad bucket and seed the scheduler's latency model
        with measured (post-compile) service times, so the first real
        admission decisions run on data, not guesses."""
        if not isinstance(self._index, LegacyIndexAdapter):
            self._index.r_min_for(self.k)
        buckets = sorted({self.batcher.bucket(s)
                          for s in range(1, self.batcher.max_batch + 1)})
        for size in buckets:
            q = jnp.zeros((size, d), jnp.float32)
            for degraded in (False, True):
                req = self._make_request(size, degraded)
                jax.block_until_ready(
                    self._index.search(q, req).dists)     # compile pass
                t0 = self.clock()
                jax.block_until_ready(self._index.search(q, req).dists)
                self.batcher.model.observe(size, degraded,
                                           max(0.0, self.clock() - t0))
