"""End-to-end serving driver: batched ANN requests against a *mutable*
DET-LSH index — the paper's deployment scenario (rapid index build,
immediate serving) extended with live traffic, now through the
epoch-pinned ``ServingRuntime`` (docs/DESIGN.md §9): points arrive and
disappear while queries run, sealing delta segments and triggering
compaction; hopeless deadlines are shed with an explicit ``Rejected``;
injected engine and compaction faults recover with bit-identical answers.
The finale snapshots the live index and restarts the service from the
snapshot — no rebuild; a durability phase serves a WAL-backed
``DurableIndex``, kills it with an un-checkpointed tail, and recovers it
bit-identically (docs/DESIGN.md §13); and a last phase serves the
*sharded* PDET index over the devices present (up to four), bit-identical
to its single-device twin (docs/DESIGN.md §7).

  PYTHONPATH=src python examples/vector_search_service.py

It runs on whatever backend JAX finds.  On a CPU-only host, give the PDET
phase a four-device mesh with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

import dataclasses
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "src")

import repro
from repro.api import IndexSpec, PlacementSpec, SearchRequest
from repro.launch import compile_cache
from repro.serving import (Answer, COMPACTION_SWAP, ENGINE_CALL, FaultPlan,
                           InjectedFault, Rejected, ServingRuntime)


def main():
    compile_cache.enable()
    rng = np.random.default_rng(1)
    n, d, n_requests = 20000, 48, 96

    centers = rng.standard_normal((32, d)).astype(np.float32)

    def draw(m):
        return (centers[rng.integers(0, 32, m)]
                + 0.25 * rng.standard_normal((m, d)).astype(np.float32))

    data = draw(n)

    t0 = time.perf_counter()
    spec = IndexSpec(kind="streaming", K=4, L=8, c=1.5, beta_override=0.05,
                     delta_capacity=1024, max_segments=3)
    index = repro.api.build(jnp.asarray(data), jax.random.key(0), spec)
    jax.block_until_ready(index.manifest.segments[0].forest.point_ids)
    print(f"index built in {time.perf_counter() - t0:.2f}s "
          f"({index.index_size_bytes() / 1e6:.1f} MB, "
          f"{index.n_live} live points)")

    # Explicit r_min pins the search radius, so every equality below —
    # retry vs baseline, restart vs live — compares like with like.
    base_req = SearchRequest(k=10, r_min=float(index.r_min_for(10)))
    plan = FaultPlan()
    # max_wait 50ms: closed-loop submits are µs apart, so bursts coalesce
    # into full buckets (one compiled batch shape) instead of fragmenting.
    rt = ServingRuntime(index, k=10, max_batch=32, pad_to=32,
                        max_wait_ms=50.0, fault_plan=plan, request=base_req)
    rt.warmup(d)

    def queries(m):
        return [data[rng.integers(0, n)]
                + 0.05 * rng.standard_normal(d).astype(np.float32)
                for _ in range(m)]

    def stream(vecs, deadline=None):
        # serve() iterates lazily, so arrivals are stamped at submit time —
        # pre-stamping a whole burst makes every request look old after the
        # first batch's service time and fragments the batching.
        return ((time.perf_counter(), v, deadline) for v in vecs)

    # Phase 1: read-only traffic against the base build.
    results = rt.serve(stream(queries(n_requests)))
    assert all(isinstance(o, Answer) for o in results)
    print(f"phase 1 (static): served {len(results)}: {rt.stats.summary()}")

    # Phase 2: live traffic — interleave upserts/deletes with query bursts.
    # Mutations are barriers (queued queries answer first); seals happen at
    # delta capacity and compaction fires via the runtime trigger.
    t0 = time.perf_counter()
    for round_ in range(4):
        fresh = draw(800)
        gids = rt.upsert(fresh)
        rt.delete(gids[::7])                       # churn: drop every 7th
        rt.delete(rng.integers(0, n, 100))         # and some base points
        burst = rt.serve(stream(queries(32)))
        assert len(burst) == 32
    rt.delete(np.arange(10**8, 10**8 + 5))         # counted no-op deletes
    print(f"phase 2 (live churn, {time.perf_counter() - t0:.2f}s): "
          f"{rt.stats.summary()}")
    print(f"index now: {index.stats()}")

    # A just-upserted point must be findable right away.
    probe = draw(1)[0]
    [gid] = rt.upsert(probe)
    ans, = rt.serve([(time.perf_counter(), probe)])
    assert int(ans.ids[0]) == int(gid) and ans.dists[0] < 1e-3
    print(f"fresh upsert gid={int(gid)} served with dist={ans.dists[0]:.2g}")

    rt.delete([gid])
    ans, = rt.serve([(time.perf_counter(), probe)])
    assert int(ans.ids[0]) != int(gid)
    print(f"...and invisible immediately after delete "
          f"(top hit now gid={int(ans.ids[0])})")

    # Load shedding is explicit: a request whose deadline already passed is
    # rejected with a reason, never silently dropped or silently late.
    past = time.perf_counter() - 1.0
    shed = rt.serve(stream(queries(8), deadline=past))
    assert all(isinstance(o, Rejected) and o.reason == "deadline"
               for o in shed)
    print(f"hopeless deadlines shed explicitly: {rt.stats.summary()['shed']}")

    fault_recovery_phase(rt, index, plan, queries, stream, base_req)

    # Snapshot the live index (segments + tombstones + un-sealed delta
    # rows) and restart the service from disk — the rebuild the paper's
    # rapid-indexing pitch exists to avoid now happens zero times.
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        index.save(tmp)
        restored = repro.api.load(tmp)
        print(f"snapshot save+load in {time.perf_counter() - t0:.2f}s "
              f"({restored.n_live} live points restored)")
        rt2 = ServingRuntime(restored, k=10, max_batch=32, pad_to=32,
                             request=base_req)
        probe2 = draw(1)[0]
        before, = rt.serve([(time.perf_counter(), probe2)])
        after, = rt2.serve([(time.perf_counter(), probe2)])
        assert np.array_equal(before.ids, after.ids)
        assert np.array_equal(before.dists, after.dists)
        print("restarted service answers bit-identically from the snapshot")

    # Phase 3: durability — serve a WAL-backed index, kill it mid-flight,
    # recover the root, and keep serving with bit-identical answers.
    kill_and_recover_phase(draw, base_req)

    # Phase 4: the sharded PDET index, served through the same runtime.
    serve_pdet(data, draw)


def kill_and_recover_phase(draw, base_req):
    """DurableIndex lifecycle (docs/DESIGN.md §13): WAL-logged mutations,
    a kill with an un-checkpointed tail, and bit-identical recovery."""
    from repro.core import derive_params
    from repro.durability import DurableIndex, recover
    from repro.streaming import StreamingDETLSH

    rng = np.random.default_rng(13)
    base = draw(4000)
    p = derive_params(K=4, c=1.5, L=8, beta_override=0.05)
    idx = StreamingDETLSH.build(jnp.asarray(base), jax.random.key(5), p,
                                delta_capacity=1024, max_segments=3)

    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "durable")
        durable = DurableIndex.create(idx, root, checkpoint_bytes=1 << 22)
        rt = ServingRuntime(durable, k=10, max_batch=32, pad_to=32,
                            request=base_req)
        t0 = time.perf_counter()
        for _ in range(3):
            gids = rt.upsert(draw(600))
            rt.delete(gids[::9])
        s = rt.stats.summary()
        print(f"\ndurability phase: {time.perf_counter() - t0:.2f}s of "
              f"WAL-logged churn (wal_bytes={s['wal_bytes']}, "
              f"fsyncs={s['fsyncs']}, checkpoints={s['checkpoints']})")

        probes = np.stack([draw(1)[0] for _ in range(16)])
        before = durable.search(jnp.asarray(probes), base_req)
        digest = durable.state_digest()
        durable.wal._f.close()       # the kill: no flush, no final snapshot

        t0 = time.perf_counter()
        recovered = recover(root)
        report = recovered.last_recovery
        print(f"recovered in {time.perf_counter() - t0:.2f}s from "
              f"{report.checkpoint}, replayed {report.n_replayed} WAL "
              f"records (torn_bytes={report.torn_bytes})")
        assert recovered.state_digest() == digest
        after = recovered.search(jnp.asarray(probes), base_req)
        assert np.array_equal(np.asarray(before.ids), np.asarray(after.ids))
        assert np.array_equal(np.asarray(before.dists),
                              np.asarray(after.dists))

        # ...and the recovered index serves + mutates like nothing happened
        rt2 = ServingRuntime(recovered, k=10, max_batch=32, pad_to=32,
                             request=base_req)
        assert rt2.stats.summary()["recovery_replayed"] == report.n_replayed
        rt2.upsert(draw(64))
        out = rt2.serve((time.perf_counter(), q) for q in probes)
        assert all(isinstance(o, Answer) for o in out)
        recovered.close()
        print("recovered index answers bit-identically and keeps serving")


def fault_recovery_phase(rt, index, plan, queries, stream, base_req):
    """Inject the §9 faults live and prove recovery is bit-identical."""
    probes = queries(32)

    # Engine-call failure: one retry on the vmap semantics-of-record
    # engine.  32 probes = exactly one batch, so the whole serve runs on
    # the retry path — and its answers must be bit-identical to a
    # fault-free serialized run on that same engine.
    retries0 = rt.stats.retries
    plan.arm(ENGINE_CALL, times=1)
    recovered = rt.serve(stream(probes))
    assert rt.stats.retries == retries0 + 1
    assert all(isinstance(o, Answer) for o in recovered)
    oracle = index.search(
        jnp.asarray(np.stack(probes)),
        dataclasses.replace(base_req, engine="vmap", n_active=len(probes)))
    oids, odists = np.asarray(oracle.ids), np.asarray(oracle.dists)
    for i, a in enumerate(recovered):
        assert np.array_equal(a.ids, oids[i])
        assert np.array_equal(a.dists, odists[i])
    print(f"engine fault: retried on vmap, {len(recovered)} answers "
          f"bit-identical to a fault-free run on the retry engine")

    # Compaction crash mid-swap: the manifest stays on the pre-swap epoch,
    # a pinned reader keeps answering identically through the crash AND
    # through the successful retry (RCU), and live traffic still matches.
    qs = jnp.asarray(np.stack(probes[:8]))
    req = dataclasses.replace(base_req, n_active=8)
    epoch = rt.pin()
    before = epoch.search(qs, req)
    v0 = index.manifest.version
    plan.arm(COMPACTION_SWAP, times=1)
    assert rt.compact(force=True) is False
    assert isinstance(rt.last_compaction_error, InjectedFault)
    assert index.manifest.version == v0          # pre-swap epoch intact
    assert rt.compact(force=True) is True        # retried swap completes
    after = epoch.search(qs, req)
    assert np.array_equal(np.asarray(before.ids), np.asarray(after.ids))
    assert np.array_equal(np.asarray(before.dists),
                          np.asarray(after.dists))
    rt.release(epoch)
    live = rt.serve(stream(probes))
    assert all(isinstance(o, Answer) for o in live)
    print(f"compaction crash: recovered to pre-swap epoch, pinned reader "
          f"bit-identical across the retried swap "
          f"(crashes={rt.stats.compaction_crashes}, "
          f"compactions={rt.stats.compactions})")


def serve_pdet(data, draw):
    n_dev = len(jax.devices())
    shards = max(s for s in (4, 2, 1) if n_dev >= s)
    base = IndexSpec(kind="static", K=4, L=8, c=1.5, beta_override=0.05,
                     leaf_size=64)
    spec = dataclasses.replace(
        base, placement=PlacementSpec(mesh_shape=(shards,),
                                      mesh_axes=("data",)))
    t0 = time.perf_counter()
    pdet = repro.api.build(jnp.asarray(data), jax.random.key(7), spec)
    det = repro.api.build(jnp.asarray(data), jax.random.key(7), base)
    print(f"\nPDET phase: {shards}-shard mesh "
          f"({time.perf_counter() - t0:.2f}s for both builds)")

    # Immutable indexes get trivial epochs — the same runtime serves them.
    rt = ServingRuntime(pdet, k=10, max_batch=32, pad_to=32)
    rt.warmup(data.shape[1])
    probes = [draw(1)[0] for _ in range(48)]
    results = rt.serve((time.perf_counter(), p) for p in probes)
    assert all(isinstance(o, Answer) for o in results)
    print(f"served {len(results)} via PDET: {rt.stats.summary()}")

    req = SearchRequest(k=10, r_min=0.5)
    a = pdet.search(jnp.asarray(np.stack(probes[:16])), req)
    b = det.search(jnp.asarray(np.stack(probes[:16])),
                   SearchRequest(k=10, r_min=0.5, engine="fused"))
    assert np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    assert np.array_equal(np.asarray(a.dists), np.asarray(b.dists))
    print(f"PDET == DET bit-identical over {shards} shards "
          f"(engine={a.stats.engine}, per-shard candidates="
          f"{np.asarray(a.stats.shard_candidates).tolist()}, "
          f"psum_rounds={int(a.stats.psum_rounds)})")

    # Sharded snapshot: per-shard files, reshard-on-load.
    with tempfile.TemporaryDirectory() as tmp:
        pdet.save(tmp)
        halved = repro.api.load(
            tmp, placement=PlacementSpec(mesh_shape=(max(shards // 2, 1),),
                                         mesh_axes=("data",)))
        c = halved.search(jnp.asarray(np.stack(probes[:16])), req)
        assert np.array_equal(np.asarray(c.ids), np.asarray(a.ids))
        print(f"snapshot resharded {shards} -> {halved.n_shards} shards; "
              f"answers unchanged")


if __name__ == "__main__":
    main()
