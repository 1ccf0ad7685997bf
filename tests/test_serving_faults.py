"""Fault-injection suite (docs/DESIGN.md §9, run as a dedicated CI step).

The §9 recovery contracts, proven rather than asserted:
  * an engine-call failure retries once on the vmap semantics-of-record
    engine and the answers are still exact; a second failure rejects only
    the affected requests — the service keeps serving;
  * a compaction crashing mid-swap leaves the manifest on the pre-swap
    epoch, pinned readers keep answering identically, and a retried
    compaction completes;
  * a failing snapshot store surfaces as the injected error, never a
    half-loaded index;
  * no injected fault can make the service return *wrong* (rather than
    rejected) answers.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api
from repro.api import SearchRequest
from repro.core import derive_params
from repro.serving import (Answer, COMPACTION_SWAP, ENGINE_CALL, FaultPlan,
                           InjectedFault, Rejected, SNAPSHOT_LOAD,
                           ServingRuntime)
from repro.streaming import StreamingDETLSH
from tests.conftest import brute_force_knn, make_clustered, make_queries_near

D = 16
SAT = dict(r_min=1e6, M=10**6)


def _runtime(rng, n=512, **kw):
    p = derive_params(K=4, c=1.5, L=4, beta_override=0.1)
    idx = StreamingDETLSH.build(
        jnp.asarray(make_clustered(rng, n, D)), jax.random.key(0), p,
        Nr=32, leaf_size=16, delta_capacity=32, max_segments=3)
    plan = FaultPlan()
    kw = {**dict(max_batch=8, pad_to=8), **kw}
    rt = ServingRuntime(idx, k=5, fault_plan=plan,
                        request=SearchRequest(k=5, **SAT), **kw)
    return rt, idx, plan


def _serve_and_check(rt, idx, queries):
    """Serve and assert every answer is the exact brute-force top-k over
    the current survivors — the 'no fault can produce wrong answers'
    oracle.  Survivor rows are mapped through their global ids (mutations
    renumber rows, answers are in gid space)."""
    data, gids = idx.pin_state().survivors()
    out = rt.serve([(time.perf_counter(), q) for q in queries])
    gt_i, gt_d = brute_force_knn(data, queries, rt.k)
    for i, o in enumerate(out):
        if isinstance(o, Rejected):
            continue
        assert set(o.ids.tolist()) == set(gids[gt_i[i]].tolist()), i
        np.testing.assert_allclose(o.dists, gt_d[i], rtol=1e-4, atol=1e-4)
    return out


# ---------------------------------------------------------------------------
# FaultPlan mechanics
# ---------------------------------------------------------------------------

def test_fault_plan_arms_fires_and_counts():
    plan = FaultPlan()
    plan.fire(ENGINE_CALL)                       # unarmed: counted, no raise
    assert plan.fired[ENGINE_CALL] == 1 and plan.raised[ENGINE_CALL] == 0
    plan.arm(ENGINE_CALL, times=2)
    assert plan.armed(ENGINE_CALL) == 2
    for _ in range(2):
        with pytest.raises(InjectedFault) as e:
            plan.fire(ENGINE_CALL, detail="boom")
        assert e.value.site == ENGINE_CALL and "boom" in str(e.value)
    plan.fire(ENGINE_CALL)                       # charges consumed
    assert plan.fired[ENGINE_CALL] == 4 and plan.raised[ENGINE_CALL] == 2
    with pytest.raises(ValueError, match="unknown fault site"):
        plan.arm("not_a_site")
    with pytest.raises(ValueError):
        plan.arm(ENGINE_CALL, times=0)


def test_fault_plan_custom_exception_type():
    plan = FaultPlan().arm(COMPACTION_SWAP, exc=OSError)
    with pytest.raises(OSError, match="injected fault at compaction_swap"):
        plan.fire(COMPACTION_SWAP)


def test_fault_plan_unknown_site_names_valid_set():
    """A typo'd site must fail loudly at arm() time, naming the valid
    sites — not silently never fire (docstring contract)."""
    from repro.serving import faults
    plan = FaultPlan()
    with pytest.raises(ValueError) as e:
        plan.arm("wal_apend")                    # the classic typo
    for site in faults.SITES:
        assert site in str(e.value)
    with pytest.raises(ValueError, match="unknown fault site"):
        plan.fire("wal_apend")
    with pytest.raises(ValueError, match="unknown fault site"):
        plan.armed("wal_apend")


def test_fault_plan_durability_sites_registered():
    from repro.serving import faults
    from repro.serving import (CHECKPOINT_INSTALL, SNAPSHOT_WRITE,
                               WAL_APPEND, WAL_FSYNC)
    assert {WAL_APPEND, WAL_FSYNC, SNAPSHOT_WRITE,
            CHECKPOINT_INSTALL} <= set(faults.SITES)


def test_fault_plan_skip_defers_armed_charges():
    """skip=k lets the first k crossings through unharmed, so a test can
    target the (k+1)-th crossing of a nested site (e.g. the *commit*
    crossing of CHECKPOINT_INSTALL)."""
    from repro.serving import WAL_APPEND
    plan = FaultPlan().arm(WAL_APPEND, times=1, skip=2)
    plan.fire(WAL_APPEND)                        # skipped
    plan.fire(WAL_APPEND)                        # skipped
    with pytest.raises(InjectedFault):
        plan.fire(WAL_APPEND)                    # the targeted crossing
    plan.fire(WAL_APPEND)                        # charges consumed
    assert plan.fired[WAL_APPEND] == 4 and plan.raised[WAL_APPEND] == 1
    with pytest.raises(ValueError, match="skip must be"):
        plan.arm(WAL_APPEND, skip=-1)


# ---------------------------------------------------------------------------
# Engine-call failures
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_engine_failure_retries_on_vmap_with_exact_answers(rng):
    rt, idx, plan = _runtime(rng)
    data, _ = idx.pin_state().survivors()
    queries = make_queries_near(data, rng, 6)
    plan.arm(ENGINE_CALL, times=1)
    out = _serve_and_check(rt, idx, queries)
    assert all(isinstance(o, Answer) for o in out)
    assert rt.stats.retries == 1
    assert plan.raised[ENGINE_CALL] == 1 and rt.stats.shed_total == 0


@pytest.mark.timeout(300)
def test_answers_name_the_engine_that_served_them(rng):
    """A retried batch is visible per answer, not only in stats.retries:
    a fused-engine failure must not pass as a fused answer."""
    rt, idx, plan = _runtime(rng)
    data, _ = idx.pin_state().survivors()
    queries = make_queries_near(data, rng, 8)        # one full batch
    out = _serve_and_check(rt, idx, queries)
    assert {o.engine for o in out} == {"fused"} and rt.stats.retries == 0
    plan.arm(ENGINE_CALL, times=1)
    out = _serve_and_check(rt, idx, queries)
    assert {o.engine for o in out} == {"vmap"} and rt.stats.retries == 1


@pytest.mark.timeout(300)
def test_persistent_engine_failure_rejects_only_affected_batch(rng):
    rt, idx, plan = _runtime(rng, max_batch=4)
    data, _ = idx.pin_state().survivors()
    queries = make_queries_near(data, rng, 8)    # two batches of 4
    plan.arm(ENGINE_CALL, times=2)               # first batch + its retry
    out = _serve_and_check(rt, idx, queries)
    rejected = [o for o in out if isinstance(o, Rejected)]
    answered = [o for o in out if isinstance(o, Answer)]
    assert len(rejected) == 4 and len(answered) == 4
    assert all(o.reason == "engine_failure" for o in rejected)
    assert rt.stats.shed["engine_failure"] == 4
    # epochs drained even through the failure path (finally-released)
    assert idx.manifest.pinned_versions() == ()
    # the service keeps serving afterwards
    out2 = _serve_and_check(rt, idx, queries[:3])
    assert all(isinstance(o, Answer) for o in out2)


# ---------------------------------------------------------------------------
# Compaction crash mid-swap
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_compaction_crash_recovers_to_pre_swap_epoch(rng):
    rt, idx, plan = _runtime(rng, n=256)
    rt.upsert(make_clustered(rng, 70, D))        # fan-out + tombstones
    rt.delete(np.arange(0, 20))
    data, _ = idx.pin_state().survivors()
    queries = jnp.asarray(make_queries_near(data, rng, 4))

    epoch = rt.pin()
    before = epoch.search(queries, SearchRequest(k=5, n_active=4, **SAT))
    v0, segs0 = idx.manifest.version, list(idx.manifest.segments)
    plan.arm(COMPACTION_SWAP, times=1)
    assert rt.compact() is False                 # crashed mid-install
    assert rt.stats.compaction_crashes == 1
    assert isinstance(rt.last_compaction_error, InjectedFault)
    # pre-swap epoch fully intact: same version, same segment list
    assert idx.manifest.version == v0
    assert len(idx.manifest.segments) == len(segs0)
    assert all(a is b for a, b in zip(idx.manifest.segments, segs0))
    during = epoch.search(queries, SearchRequest(k=5, n_active=4, **SAT))
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(during.ids))
    # retried compaction completes and the pinned reader still answers
    # identically (RCU: the swap happened underneath it)
    assert rt.compact() is True
    after = epoch.search(queries, SearchRequest(k=5, n_active=4, **SAT))
    np.testing.assert_array_equal(np.asarray(before.ids),
                                  np.asarray(after.ids))
    np.testing.assert_array_equal(np.asarray(before.dists),
                                  np.asarray(after.dists))
    rt.release(epoch)
    # live queries after the crash+recovery are exact too
    _serve_and_check(rt, idx, np.asarray(queries))


@pytest.mark.timeout(300)
def test_compaction_crash_during_upsert_trigger_keeps_serving(rng):
    """maybe_compact firing inside the upsert path crashes: the upsert
    itself must stand (rows inserted), the crash is counted, and a later
    compaction succeeds."""
    rt, idx, plan = _runtime(rng, n=256)
    plan.arm(COMPACTION_SWAP, times=1)
    # enough seals to cross max_segments and trigger compaction
    rt.upsert(make_clustered(rng, 140, D))
    assert rt.stats.compaction_crashes == 1
    assert idx.n_live == 256 + 140               # upsert survived the crash
    data, _ = idx.pin_state().survivors()
    _serve_and_check(rt, idx, make_queries_near(data, rng, 5))
    assert rt.compact() is True                  # recovery compaction


# ---------------------------------------------------------------------------
# Snapshot-load boundary
# ---------------------------------------------------------------------------

@pytest.mark.timeout(300)
def test_snapshot_load_fault_surfaces_not_half_loads(rng, tmp_path):
    p = derive_params(K=2, c=1.5, L=2, beta_override=0.1)
    idx = StreamingDETLSH.build(
        jnp.asarray(make_clustered(rng, 64, 8)), jax.random.key(0), p,
        Nr=8, leaf_size=8, delta_capacity=16, max_segments=2)
    idx.save(tmp_path / "snap")
    plan = FaultPlan().arm(SNAPSHOT_LOAD, times=1)
    with plan.installed_on_load():
        with pytest.raises(InjectedFault) as e:
            repro.api.load(str(tmp_path / "snap"))
        assert e.value.site == SNAPSHOT_LOAD
        assert "snap" in e.value.detail          # names the offending path
        # charge consumed: the next load succeeds and still counts fires
        reloaded = repro.api.load(str(tmp_path / "snap"))
    assert plan.fired[SNAPSHOT_LOAD] == 2
    assert reloaded.n_live == idx.n_live
    # the hook uninstalled cleanly on context exit
    from repro.api import persist
    assert persist.load_fault_hook is None
