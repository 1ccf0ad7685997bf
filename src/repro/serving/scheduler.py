"""Deadline-aware micro-batching + admission control (docs/DESIGN.md §9).

Requests carry an absolute deadline (or none).  The ``MicroBatcher``
coalesces arrivals into the bounded pad-to-bucket batch shapes the jitted
query path already compiles for, and decides *when* to flush and *what* to
admit:

  flush when   batch is full · the oldest request has waited ``max_wait``
               · deadline pressure (waiting longer would make the earliest
                 deadline unmeetable under the current latency model)
  admit        requests predicted to meet their deadline
  degrade      when a full-effort batch would miss deadlines, re-plan the
               batch at a capped ``max_rounds`` (recorded ``degraded=True``)
               — graceful degradation strictly *before* shedding
  shed         only requests that still cannot meet their deadline (or that
               overflow the bounded queue) — always an explicit ``Rejected``
               outcome, never a silent drop

The latency model is an EWMA per (pad bucket, degraded) key, seeded by the
runtime's warmup measurements, so admission decisions are driven by what
this process actually measured, not constants.  The scheduler holds no jax
state and never touches the index — it is pure queueing logic, unit-tested
with a fake clock (tests/test_serving_runtime.py).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Request:
    """One enqueued query: vector + arrival + optional absolute deadline
    (same clock domain as the runtime's ``clock``)."""

    rid: int
    query: np.ndarray
    arrival: float
    deadline: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Rejected:
    """Explicit load-shed outcome — the runtime never silently drops."""

    rid: int
    reason: str          # 'deadline' | 'queue_full' | 'engine_failure'
    detail: str = ""


@dataclasses.dataclass(frozen=True)
class Answer:
    rid: int
    ids: np.ndarray
    dists: np.ndarray
    epoch: int           # epoch id the batch was pinned to
    degraded: bool       # answered at capped max_rounds
    latency_ms: float
    engine: str = ""     # query engine that served the batch (after retry)
    queue_ms: float = 0.0  # arrival -> its batch's start, <= latency_ms


REJECT_REASONS = ("deadline", "queue_full", "engine_failure")


class LatencyModel:
    """EWMA service-time estimates per (pad bucket, degraded) key."""

    def __init__(self, alpha: float = 0.3, degrade_guess: float = 0.5):
        self.alpha = alpha
        self.degrade_guess = degrade_guess     # degraded/normal ratio prior
        self._ewma: dict = {}

    def observe(self, bucket: int, degraded: bool, seconds: float) -> None:
        key = (bucket, degraded)
        prev = self._ewma.get(key)
        self._ewma[key] = seconds if prev is None else \
            (1 - self.alpha) * prev + self.alpha * seconds

    def predict(self, bucket: int, degraded: bool = False) -> float:
        """Expected service seconds; optimistic 0.0 before any sample (we
        admit until the model has measured — a cold service must not shed
        its very first requests on a guess)."""
        got = self._ewma.get((bucket, degraded))
        if got is not None:
            return got
        if degraded:
            base = self._ewma.get((bucket, False))
            if base is not None:
                return base * self.degrade_guess
        return 0.0


class MicroBatcher:
    """Bounded FIFO request queue + the flush/admit/degrade/shed policy."""

    def __init__(self, *, max_batch: int = 32, pad_to: int = 32,
                 max_wait: float = 0.002, deadline_headroom: float = 1.0,
                 queue_cap: Optional[int] = None,
                 latency_model: Optional[LatencyModel] = None):
        if max_batch < 1 or pad_to < 1:
            raise ValueError(f"max_batch/pad_to must be >= 1, got "
                             f"{max_batch}/{pad_to}")
        self.max_batch = max_batch
        self.pad_to = pad_to
        self.max_wait = max_wait
        self.deadline_headroom = deadline_headroom
        self.queue_cap = queue_cap
        self.model = latency_model or LatencyModel()
        self._queue: deque = deque()

    # ------------------------------------------------------------------
    # Queue
    # ------------------------------------------------------------------

    def __len__(self) -> int:
        return len(self._queue)

    @property
    def depth(self) -> int:
        return len(self._queue)

    def bucket(self, size: int) -> int:
        """Pad bucket for a partial batch: next multiple of ``pad_to``
        (bounded compile set — serving/lsh_service.py's contract)."""
        return min(self.max_batch, -(-size // self.pad_to) * self.pad_to)

    def enqueue(self, req: Request) -> Optional[Rejected]:
        """Append; returns a ``Rejected('queue_full')`` instead of growing
        past the bounded queue (explicit backpressure, never OOM)."""
        if self.queue_cap is not None and len(self._queue) >= self.queue_cap:
            return Rejected(req.rid, "queue_full",
                            f"queue depth {len(self._queue)} at cap "
                            f"{self.queue_cap}")
        self._queue.append(req)
        return None

    # ------------------------------------------------------------------
    # Flush policy
    # ------------------------------------------------------------------

    def _head(self, count: Optional[int] = None) -> list:
        count = len(self._queue) if count is None else count
        return [self._queue[i] for i in range(min(count, len(self._queue)))]

    def ready(self, now: float) -> bool:
        """Should the head batch flush now?"""
        if not self._queue:
            return False
        if len(self._queue) >= self.max_batch:
            return True
        if now - self._queue[0].arrival >= self.max_wait:
            return True
        head = self._head(self.max_batch)
        deadlines = [r.deadline for r in head if r.deadline is not None]
        if deadlines:
            pred = self.model.predict(self.bucket(len(head)))
            # waiting longer would push the earliest deadline past its
            # predicted completion — flush under deadline pressure
            if min(deadlines) - now <= pred * self.deadline_headroom:
                return True
        return False

    def next_batch(self, now: float
                   ) -> Tuple[List[Request], bool, List[Rejected]]:
        """Pop the head batch and run admission control on it.

        Returns ``(admitted, degraded, shed)``: the requests to run, at
        full effort or degraded, plus the explicit rejections.  Degrade is
        always tried before shedding a deadline: a capped-``max_rounds``
        batch is predicted cheaper, so requests that would miss at full
        effort may still be served degraded.
        """
        batch = [self._queue.popleft()
                 for _ in range(min(self.max_batch, len(self._queue)))]
        shed: List[Rejected] = []

        def misses(reqs, degraded):
            pred = self.model.predict(self.bucket(len(reqs)), degraded)
            lat = pred * self.deadline_headroom
            return [r for r in reqs
                    if r.deadline is not None and now + lat > r.deadline]

        degraded = False
        missing = misses(batch, degraded=False)
        if missing:
            # graceful degradation before any shed: can a capped-effort
            # batch bring the misses back inside their deadlines?
            still = misses(batch, degraded=True)
            if len(still) < len(missing):
                degraded = True
                missing = still
        if missing:
            dead = set(r.rid for r in missing)
            shed = [Rejected(r.rid, "deadline",
                             f"predicted completion past deadline by "
                             f"{max(0.0, now - (r.deadline or now)):.4f}s "
                             f"queue+service") for r in missing]
            batch = [r for r in batch if r.rid not in dead]
            if degraded and not misses(batch, degraded=False):
                degraded = False       # shed freed enough budget: full effort
        return batch, degraded, shed

    def drain(self) -> List[Request]:
        """Remove and return everything still queued (shutdown path)."""
        out = list(self._queue)
        self._queue.clear()
        return out
