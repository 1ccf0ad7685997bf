"""ServingRuntime: epoch pinning, micro-batching, admission control,
metrics (docs/DESIGN.md §9).

Scheduler policy is tested with a fake clock (pure queueing logic, no
jax); the runtime tests drive a real streaming index and check the §9
contracts: mutation barriers, epoch stability across compaction, counted
no-op deletes, gid-exhaustion recovery without losing queued requests,
and the bounded latency ring.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SearchRequest
from repro.core import derive_params
from repro.serving import (Answer, LatencyModel, LatencyRing, MicroBatcher,
                           Rejected, Request, ServingRuntime)
from repro.streaming import StreamingDETLSH
from tests.conftest import brute_force_knn, make_clustered, make_queries_near

D = 16
SAT = dict(r_min=1e6, M=10**6)      # saturating: exact brute-force answers


def _build_index(rng, n=1024, **kw):
    p = derive_params(K=4, c=1.5, L=4, beta_override=0.1)
    kw = {**dict(Nr=32, leaf_size=16, delta_capacity=32, max_segments=3),
          **kw}
    return StreamingDETLSH.build(
        jnp.asarray(make_clustered(rng, n, D)), jax.random.key(0), p, **kw)


# ---------------------------------------------------------------------------
# LatencyRing
# ---------------------------------------------------------------------------

def test_latency_ring_is_bounded_and_list_like():
    ring = LatencyRing(capacity=8)
    assert len(ring) == 0 and np.isnan(ring.percentile(50))
    for v in range(5):
        ring.append(float(v))
    assert len(ring) == 5 and ring.total == 5
    np.testing.assert_array_equal(ring.values(), [0, 1, 2, 3, 4])
    for v in range(5, 20):
        ring.append(float(v))
    # bounded: only the most recent 8 samples retained, oldest first
    assert len(ring) == 8 and ring.total == 20
    np.testing.assert_array_equal(ring.values(), np.arange(12, 20))
    # list-protocol interop the old unbounded list offered
    assert list(ring) == list(np.arange(12.0, 20.0))
    assert float(np.percentile(ring, 50)) == ring.percentile(50)
    assert ring.percentile(0) == 12.0 and ring.percentile(100) == 19.0


def test_service_stats_ring_keeps_percentile_api(rng):
    """Satellite regression: ServiceStats.latencies_ms is now a bounded
    ring but percentile()/summary() behave exactly as before."""
    from repro.serving.lsh_service import ServiceStats
    stats = ServiceStats()
    assert len(stats.latencies_ms) == 0
    assert np.isnan(stats.percentile(50))
    for v in range(10):
        stats.latencies_ms.append(float(v))
    assert stats.percentile(50) == 4.5
    s = stats.summary()
    assert set(s) == {"queries", "batches", "pad_queries", "upserts",
                      "deletes", "compactions", "p50_ms", "p99_ms"}
    assert stats.latencies_ms.capacity == 4096       # O(1) memory forever


# ---------------------------------------------------------------------------
# Scheduler (fake clock — no jax)
# ---------------------------------------------------------------------------

def _req(rid, arrival, deadline=None):
    return Request(rid=rid, query=np.zeros(D, np.float32), arrival=arrival,
                   deadline=deadline)


def test_batcher_flushes_on_full_and_max_wait():
    mb = MicroBatcher(max_batch=4, pad_to=4, max_wait=0.010)
    assert not mb.ready(now=0.0)
    for i in range(3):
        assert mb.enqueue(_req(i, arrival=0.0)) is None
    assert not mb.ready(now=0.005)          # partial, under max_wait
    assert mb.ready(now=0.011)              # oldest waited past max_wait
    mb.enqueue(_req(3, arrival=0.001))
    assert mb.ready(now=0.002)              # full batch flushes immediately
    batch, degraded, shed = mb.next_batch(now=0.002)
    assert [r.rid for r in batch] == [0, 1, 2, 3]
    assert not degraded and not shed


def test_batcher_queue_cap_rejects_explicitly():
    mb = MicroBatcher(max_batch=4, pad_to=4, queue_cap=2)
    assert mb.enqueue(_req(0, 0.0)) is None
    assert mb.enqueue(_req(1, 0.0)) is None
    rej = mb.enqueue(_req(2, 0.0))
    assert isinstance(rej, Rejected) and rej.reason == "queue_full"
    assert rej.rid == 2 and len(mb) == 2    # never silently grows


def test_batcher_flushes_under_deadline_pressure():
    model = LatencyModel()
    model.observe(4, False, 0.050)          # batches take ~50ms
    mb = MicroBatcher(max_batch=4, pad_to=4, max_wait=10.0,
                      latency_model=model)
    mb.enqueue(_req(0, arrival=0.0, deadline=0.200))
    assert not mb.ready(now=0.010)          # 190ms margin >> 50ms predicted
    assert mb.ready(now=0.160)              # waiting longer would miss it


def test_batcher_sheds_unmeetable_deadlines():
    model = LatencyModel()
    model.observe(4, False, 0.050)
    model.observe(4, True, 0.050)           # degrading would not help
    mb = MicroBatcher(max_batch=4, pad_to=4, latency_model=model)
    mb.enqueue(_req(0, arrival=0.0, deadline=0.010))   # unmeetable
    mb.enqueue(_req(1, arrival=0.0, deadline=10.0))    # fine
    mb.enqueue(_req(2, arrival=0.0))                   # no deadline
    batch, degraded, shed = mb.next_batch(now=0.0)
    assert [r.rid for r in batch] == [1, 2]
    assert [s.rid for s in shed] == [0]
    assert shed[0].reason == "deadline" and not degraded


def test_batcher_degrades_before_shedding():
    model = LatencyModel()
    model.observe(4, False, 0.100)          # full effort would miss
    model.observe(4, True, 0.010)           # capped effort meets it
    mb = MicroBatcher(max_batch=4, pad_to=4, latency_model=model)
    mb.enqueue(_req(0, arrival=0.0, deadline=0.050))
    batch, degraded, shed = mb.next_batch(now=0.0)
    assert [r.rid for r in batch] == [0]
    assert degraded and not shed            # degrade strictly before shed


def test_batcher_cold_model_admits_everything():
    mb = MicroBatcher(max_batch=4, pad_to=4)
    mb.enqueue(_req(0, arrival=0.0, deadline=0.001))
    batch, degraded, shed = mb.next_batch(now=0.0)
    assert len(batch) == 1 and not shed     # no measurement -> no shedding


# ---------------------------------------------------------------------------
# Runtime over a live index
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_runtime_serves_exact_answers_and_counts(rng):
    idx = _build_index(rng)
    data, _ = idx.pin_state().survivors()
    # max_wait pinned high: batches flush on size only, so the grouping
    # (8 + 8 + 4) is deterministic regardless of wall-clock jitter
    rt = ServingRuntime(idx, k=5, max_batch=8, pad_to=8, max_wait_ms=1e6,
                        request=SearchRequest(k=5, **SAT))
    queries = make_queries_near(data, rng, 20)
    out = rt.serve([(time.perf_counter(), q) for q in queries])
    assert len(out) == 20 and all(isinstance(o, Answer) for o in out)
    gt_i, gt_d = brute_force_knn(data, queries, 5)
    for i, ans in enumerate(out):
        assert set(ans.ids.tolist()) == set(gt_i[i].tolist())
        np.testing.assert_allclose(ans.dists, gt_d[i], rtol=1e-4, atol=1e-4)
    s = rt.stats.summary()
    assert s["queries"] == 20 and s["batches"] == 3
    assert s["pad_queries"] == 4 and s["shed_total"] == 0
    assert s["epochs_pinned"] == 3 and len(rt.stats.latencies) == 20
    assert s["p99_ms"] >= s["p50_ms"] > 0
    assert idx.manifest.pinned_versions() == ()     # all epochs drained


@pytest.mark.timeout(300)
def test_warm_runtime_compiles_nothing_at_any_fill_level(rng):
    """After ``warmup`` every bucket's program is compiled: a batch of any
    fill level reuses its bucket's program (``n_active`` is a traced
    operand), so serving traces, lowers and compiles nothing."""
    from repro.core import DETLSH
    events = []

    def on_duration(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    data = make_clustered(rng, 1024, D)
    idx = DETLSH.build(jnp.asarray(data), jax.random.key(0),
                       derive_params(K=4, c=1.5, L=4, beta_override=0.1),
                       leaf_size=16)
    rt = ServingRuntime(idx, k=5, max_batch=16, pad_to=8, max_wait_ms=1e6)
    rt.warmup(D)
    queries = make_queries_near(data, rng, 16)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for fill in range(1, 17):
            rids = [rt.submit(q) for q in queries[:fill]]
            assert rt.flush() == 1
            assert all(isinstance(rt.outcomes[r], Answer) for r in rids)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert events == []
    s = rt.stats.summary()
    assert s["batches"] == 16 and s["queries"] == 136
    assert s["pad_queries"] == sum((8 if f <= 8 else 16) - f
                                   for f in range(1, 17))


@pytest.mark.timeout(300)
def test_pinned_epoch_survives_concurrent_compaction(rng):
    """Satellite: compaction triggered concurrently with an in-flight
    pinned epoch does not invalidate that epoch's answers."""
    idx = _build_index(rng, n=512, max_segments=10)
    rt = ServingRuntime(idx, k=5, request=SearchRequest(k=5, **SAT))
    rt.upsert(make_clustered(rng, 100, D))          # sealed segments +
    rt.delete(np.arange(0, 30))                     # tombstones to merge
    queries = jnp.asarray(make_clustered(rng, 4, D))

    epoch = rt.pin()
    assert idx.manifest.pinned_versions() != ()
    before = epoch.search(queries, SearchRequest(k=5, n_active=4, **SAT))
    assert rt.compact()                             # swap under the reader
    after = epoch.search(queries, SearchRequest(k=5, n_active=4, **SAT))
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))
    np.testing.assert_array_equal(np.asarray(before.dists),
                                  np.asarray(after.dists))
    rt.release(epoch)
    assert idx.manifest.pinned_versions() == ()     # retired on drain
    assert rt.stats.epochs_retired == 1


@pytest.mark.timeout(300)
def test_mutations_are_barriers_and_noops_counted(rng):
    idx = _build_index(rng, n=256)
    rt = ServingRuntime(idx, k=3, max_batch=8, pad_to=8,
                        request=SearchRequest(k=3, **SAT))
    probe = np.asarray(idx.pin_state().survivors()[0][0] + 40.0, np.float32)
    [gid] = rt.upsert(probe)
    rid = rt.submit(probe)
    # the delete flushes the queued query first (mutation barrier): the
    # queued request answers on pre-delete state, in submission order
    rt.delete([gid])
    assert int(rt.outcomes[rid].ids[0]) == int(gid)
    rid2 = rt.submit(probe)
    rt.flush()
    assert int(rt.outcomes[rid2].ids[0]) != int(gid)
    # never-inserted gids: counted no-op, not an error
    removed = rt.delete([10 ** 6, 10 ** 6 + 1])
    assert removed == 0 and rt.stats.noop_deletes == 2
    assert rt.stats.deletes == 1


@pytest.mark.timeout(300)
def test_gid_exhaustion_recovers_without_losing_queued_requests(rng):
    """Satellite: gid-space exhaustion mid-serve raises after the barrier
    flush and before any index mutation — queued requests all answer, and
    grow_id_capacity + resubmit completes the upsert."""
    idx = _build_index(rng, n=128, id_capacity=140)
    rt = ServingRuntime(idx, k=3, request=SearchRequest(k=3, **SAT))
    queries = make_clustered(rng, 5, D)
    rids = [rt.submit(q) for q in queries]
    big = make_clustered(rng, 64, D)                # would pass id_capacity
    with pytest.raises(ValueError, match="gid space exhausted"):
        rt.upsert(big)
    # every queued request was flushed and answered before the failure
    assert all(isinstance(rt.outcomes[r], Answer) for r in rids)
    assert rt.stats.shed_total == 0
    n_before = idx.n_live
    idx.grow_id_capacity(4096)
    assert len(rt.upsert(big)) == 64                # recovery completes
    assert idx.n_live == n_before + 64
    out = rt.serve([(time.perf_counter(), q) for q in queries])
    assert all(isinstance(o, Answer) for o in out)  # still serving


@pytest.mark.timeout(300)
def test_runtime_sheds_on_queue_cap_and_records_outcome(rng):
    idx = _build_index(rng, n=256)
    rt = ServingRuntime(idx, k=3, max_batch=4, pad_to=4, queue_cap=2,
                        request=SearchRequest(k=3, **SAT))
    queries = make_clustered(rng, 4, D)
    rids = [rt.submit(q) for q in queries]
    rejected = [r for r in rids if isinstance(rt.outcomes.get(r), Rejected)]
    assert len(rejected) == 2                       # cap=2: last two shed
    assert all(rt.outcomes[r].reason == "queue_full" for r in rejected)
    rt.flush()
    assert rt.stats.shed["queue_full"] == 2
    assert all(isinstance(rt.outcomes[r], Answer)
               for r in rids if r not in rejected)


@pytest.mark.timeout(300)
def test_runtime_degrades_under_deadline_pressure(rng):
    """An unmeetable deadline at full effort but meetable degraded serves
    degraded (capped max_rounds), recording degraded=True — before ever
    shedding."""
    idx = _build_index(rng, n=256)
    rt = ServingRuntime(idx, k=3, max_batch=4, pad_to=4,
                        degraded_max_rounds=1,
                        request=SearchRequest(k=3, **SAT))
    # force the model: full effort 100ms, degraded 1ms
    rt.batcher.model.observe(4, False, 0.100)
    rt.batcher.model.observe(4, True, 0.001)
    now = time.perf_counter()
    rid = rt.submit(idx.pin_state().survivors()[0][0], deadline=now + 0.050)
    rt.flush()
    ans = rt.outcomes[rid]
    assert isinstance(ans, Answer) and ans.degraded
    assert rt.stats.degraded_batches == 1 and rt.stats.shed_total == 0


@pytest.mark.timeout(300)
def test_runtime_splits_latency_into_queue_wait_and_service(rng):
    """Each answer's queue wait runs from its arrival to its batch's
    start, the rest of its latency is the batch's service; with a clock
    that ticks 0.25 s a read, both are exact."""
    idx = _build_index(rng, n=256)
    ticks = iter(np.arange(100.0, 200.0, 0.25))
    rt = ServingRuntime(idx, k=3, max_batch=2, pad_to=2,
                        clock=lambda: float(next(ticks)),
                        request=SearchRequest(k=3, **SAT))
    queries = make_clustered(rng, 3, D)
    rids = [rt.submit(q, arrival=a)
            for q, a in zip(queries, (99.0, 99.5, 100.0))]
    rt.flush()
    # batch 1 reads 100.0 (start), 100.25, 100.5 (done); batch 2 100.75,
    # 101.0, 101.25
    out = [rt.outcomes[r] for r in rids]
    assert [a.queue_ms for a in out] == [1000.0, 500.0, 750.0]
    assert [a.latency_ms for a in out] == [1500.0, 1000.0, 1250.0]
    assert all(a.queue_ms <= a.latency_ms for a in out)
    assert {a.latency_ms - a.queue_ms for a in out} == {500.0}
    np.testing.assert_array_equal(rt.stats.queue_waits.values(),
                                  [1000.0, 500.0, 750.0])
    s = rt.stats.summary()
    assert s["queue_p50_ms"] == 750.0
    assert s["queue_p99_ms"] == float(np.percentile([1000, 500, 750], 99))
    assert s["queue_p99_ms"] <= s["p99_ms"]


def test_queue_wait_ring_shares_the_latency_capacity(rng):
    idx = _build_index(rng, n=128)
    rt = ServingRuntime(idx, k=3, latency_ring_capacity=16)
    assert rt.stats.queue_waits.capacity == 16
    assert rt.stats.latencies.capacity == 16
    assert np.isnan(rt.stats.summary()["queue_p50_ms"])
    assert Answer(rid=0, ids=np.zeros(1), dists=np.zeros(1), epoch=0,
                  degraded=False, latency_ms=1.0).queue_ms == 0.0
