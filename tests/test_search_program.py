"""One compiled search program per (shapes, static config).

Each built-in engine runs its whole search — projection, state set-up,
the radius-round loop and the final top-k — as one jitted program, with
the starting radius and the active-lane count as traced scalars.  A warm
search therefore traces, lowers and compiles nothing, whatever its
``r_min`` or ``n_active``, and answers exactly as the engine's body run
eagerly (the path before the program existed) and as a nested
``jax.jit`` of ``knn_query_batch``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SearchRequest
from repro.core import DETLSH, derive_params, estimate_r_min
from repro.core.query import (QueryConfig, fused_query_batch, knn_query,
                              knn_query_batch, live_in_sorted_order,
                              make_fused_plan)
from tests.conftest import make_clustered

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
B = 16


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(7)
    data = make_clustered(rng, 2048, 16)
    queries = jnp.asarray(make_clustered(rng, B, 16))
    p = derive_params(K=4, c=1.5, L=4, beta_override=0.1)
    idx = DETLSH.build(jnp.asarray(data), jax.random.key(3), p,
                       leaf_size=32)
    r0 = estimate_r_min(idx.data, queries, 10, p.c)
    return idx, queries, r0


@pytest.fixture
def compile_events():
    """Names of the trace / lowering / compile events fired while the
    test runs."""
    seen = []

    def on_duration(event, duration, **_):
        if event in COMPILE_EVENTS:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _search(idx, queries, **req):
    res = idx.search(queries, SearchRequest(k=10, **req))
    jax.block_until_ready((res.ids, res.dists, res.stats.rounds))
    return res


@pytest.mark.parametrize("engine", ["fused", "vmap"])
@pytest.mark.parametrize("varying", ["repeat", "n_active", "r_min"])
def test_warm_search_compiles_nothing(built, compile_events, engine,
                                      varying):
    idx, queries, r0 = built
    requests = {
        "repeat": [dict()] * 3,
        "n_active": [dict(n_active=n) for n in (1, 7, 12)],
        "r_min": [dict(r_min=r0 * s) for s in (0.5, 1.0, 3.0)],
    }[varying]
    _search(idx, queries, engine=engine)                    # warm
    _search(idx, queries, engine=engine, r_min=r0)
    compile_events.clear()
    for req in requests:
        res = _search(idx, queries, engine=engine, **req)
        assert res.stats.engine == engine
    assert compile_events == []


def _tombstones(idx):
    live = np.random.default_rng(5).random(idx.n_points) > 0.2
    return jnp.asarray(live)


def _eager_fused(idx, queries, cfg, live, live_sorted, n_active):
    if live_sorted is None and live is not None:
        live_sorted = live_in_sorted_order(idx.forest, live)
    return fused_query_batch(idx.data, idx.forest, idx.A, idx.params,
                             queries, cfg, idx.fused_plan(),
                             live_sorted=live_sorted, n_active=n_active)


def _eager_vmap(idx, queries, cfg, live, live_sorted, n_active):
    active = (jnp.ones((B,), jnp.bool_) if n_active is None
              else jnp.arange(B) < n_active)
    fn = functools.partial(knn_query, idx.data, idx.forest, idx.A,
                           idx.params, cfg=cfg, live=live)
    return jax.vmap(lambda q, a: fn(q, active=a))(queries, active)


# Each engine's body run eagerly, as ``DETLSH.search`` ran it before each
# engine compiled one program: ``r_min`` and ``n_active`` concrete.
EAGER = {"fused": _eager_fused, "vmap": _eager_vmap}


def _knn(run, data, forest, A, queries, plan, live, live_sorted):
    return run(data, forest, A, queries=queries, plan=plan, live=live,
               live_sorted=live_sorted)


# the vmap engine takes id-order tombstones only
@pytest.mark.parametrize("engine,case", [
    ("fused", "plain"), ("fused", "partial"), ("fused", "live"),
    ("fused", "live_sorted"), ("vmap", "plain"), ("vmap", "partial"),
    ("vmap", "live")])
def test_program_matches_eager_and_nested_jit(built, engine, case):
    idx, queries, r0 = built
    live = _tombstones(idx) if case in ("live", "live_sorted") else None
    live_sorted = None
    if case == "live_sorted":
        live_sorted, live = live_in_sorted_order(idx.forest, live), None
    n_active = 11 if case == "partial" else None
    cfg = QueryConfig(k=10, M=8, r_min=r0, engine=engine)
    plan = idx.fused_plan()                  # the vmap engine ignores it
    # arrays go in as operands: closed over, XLA would fold them as
    # constants and round the point norms differently
    run = functools.partial(knn_query_batch, params=idx.params, cfg=cfg,
                            n_active=n_active)
    args = (idx.data, idx.forest, idx.A, queries, plan, live, live_sorted)
    got = _knn(run, *args)
    nested = jax.jit(functools.partial(_knn, run))(*args)
    eager = EAGER[engine](idx, queries, cfg, live, live_sorted, n_active)
    for ref in (nested, eager):
        for field in ("ids", "dists", "rounds", "n_candidates", "final_r"):
            np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                          np.asarray(getattr(ref, field)),
                                          err_msg=field)
    if n_active is not None:
        assert (np.asarray(got.rounds)[n_active:] == 0).all()
    if live is not None:
        ids = np.asarray(got.ids)
        real = ids[ids < idx.n_points]
        assert real.size and np.asarray(live)[real].all()


def test_search_matches_knn_query_batch(built):
    """``DETLSH.search`` answers as the engine entry it dispatches to."""
    idx, queries, r0 = built
    res = _search(idx, queries, r_min=r0, n_active=9)
    cfg = QueryConfig(k=10, M=8, r_min=r0, engine="fused")
    ref = jax.jit(lambda data, forest, A, q: knn_query_batch(
        data, forest, A, idx.params, q, cfg, make_fused_plan(data, forest),
        n_active=9))(idx.data, idx.forest, idx.A, queries)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
