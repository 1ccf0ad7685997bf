"""Behavior of the unified repro.api surface: eager config validation,
engine-registry resolution rules, protocol conformance of both index
kinds, the per-(index, k) r_min cache, and the deprecation shims."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.api import (AnnIndex, IndexSpec, LegacyIndexAdapter,
                       MutableAnnIndex, SearchRequest, as_ann_index,
                       available_engines, resolve_engine)
from repro.core import DETLSH
from repro.core.query import QueryConfig
from tests.conftest import make_clustered, make_queries_near

D = 16


@pytest.fixture(scope="module")
def static_idx():
    rng = np.random.default_rng(0)
    data = make_clustered(rng, 1024, D)
    spec = IndexSpec(kind="static", K=4, L=4, c=1.5, beta_override=0.1,
                     Nr=32, leaf_size=16)
    idx = repro.api.build(jnp.asarray(data), jax.random.key(0), spec)
    return idx, data, rng


# ---------------------------------------------------------------------------
# Eager validation (satellite: actionable errors, not deep-loop misbehavior)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kwargs", [
    dict(k=0), dict(k=-3), dict(M=0), dict(max_rounds=0), dict(r_min=0.0),
    dict(r_min=-1.0), dict(mode="lief"), dict(engine="fussed"),
    dict(dist_impl="cuda"), dict(bounds_impl="nope"), dict(n_active=-1),
])
def test_search_request_rejects_bad_fields(kwargs):
    with pytest.raises(ValueError):
        SearchRequest(**kwargs)


@pytest.mark.parametrize("kwargs", [
    dict(kind="sharded"), dict(K=0), dict(L=0), dict(c=1.0), dict(c=0.5),
    dict(Nr=1), dict(leaf_size=0), dict(breakpoint_method="quantile"),
    dict(engine="fussed"), dict(delta_capacity=0), dict(max_segments=0),
    dict(id_capacity=0), dict(project_impl="cuda"), dict(beta_override=-0.1),
])
def test_index_spec_rejects_bad_fields(kwargs):
    with pytest.raises(ValueError):
        IndexSpec(**kwargs)


@pytest.mark.parametrize("kwargs", [
    dict(k=0), dict(M=0), dict(max_rounds=0), dict(r_min=0.0),
    dict(mode="lief"), dict(engine="fussed"), dict(dist_impl="cuda"),
    dict(block_q=0),
])
def test_query_config_rejects_bad_fields(kwargs):
    with pytest.raises(ValueError):
        QueryConfig(**kwargs)


def test_error_messages_name_the_valid_choices():
    with pytest.raises(ValueError, match="vmap"):
        SearchRequest(engine="typo")
    with pytest.raises(ValueError, match="strict"):
        SearchRequest(mode="typo")
    with pytest.raises(ValueError, match="streaming"):
        IndexSpec(kind="typo")


# ---------------------------------------------------------------------------
# Engine registry resolution (replaces _pick_engine string matching)
# ---------------------------------------------------------------------------

def test_resolution_rules():
    assert resolve_engine("auto", mode="leaf", batch=64) == "fused"
    assert resolve_engine("auto", mode="leaf", batch=2) == "vmap"
    assert resolve_engine("auto", mode="leaf", batch=None) == "fused"
    # explicit fused ignores min_batch
    assert resolve_engine("fused", mode="leaf", batch=1) == "fused"
    assert resolve_engine("vmap", mode="leaf", batch=64) == "vmap"
    # strict-mode fallback is explicit: fused does not support strict
    assert resolve_engine("auto", mode="strict", batch=64) == "vmap"
    assert resolve_engine("fused", mode="strict", batch=64) == "vmap"
    assert resolve_engine(None, mode="leaf", batch=64) == "fused"
    with pytest.raises(ValueError, match="auto"):
        resolve_engine("typo", mode="leaf", batch=64)


def test_registry_round_trip_custom_engine():
    from repro.api import get_engine, register_engine
    from repro.api import registry as reg
    calls = []

    def run(*a, **kw):
        calls.append(1)
        return get_engine("vmap").run(*a, **kw)

    register_engine("test-echo", run, modes=("leaf",), min_batch=1,
                    priority=99)
    try:
        assert available_engines()[0] == "test-echo"
        assert resolve_engine("auto", mode="leaf", batch=64) == "test-echo"
        SearchRequest(engine="test-echo")    # validation accepts it
    finally:
        del reg._ENGINES["test-echo"]
    assert resolve_engine("auto", mode="leaf", batch=64) == "fused"


def test_custom_engine_registered_after_a_compiled_search_is_called(
        static_idx):
    """Engine resolution stays on the host: an engine registered after the
    fused engine compiled its program still takes over ``'auto'``, and need
    not be traceable."""
    from repro.api import get_engine, register_engine
    from repro.api import registry as reg
    idx, data, rng = static_idx
    q = jnp.asarray(make_queries_near(data, rng, 16))
    req = SearchRequest(k=5, r_min=0.5)
    fused = idx.search(q, req)
    assert fused.stats.engine == "fused"
    calls = []

    def run(*a, **kw):
        calls.append(kw["n_active"])
        res = get_engine("vmap").run(*a, **kw)
        np.asarray(res.ids)                  # host work: not traceable
        return res

    register_engine("test-late", run, modes=("leaf",), min_batch=1,
                    priority=99)
    try:
        res = idx.search(q, req)
    finally:
        del reg._ENGINES["test-late"]
    assert calls == [None] and res.stats.engine == "test-late"
    vmap = idx.search(q, SearchRequest(k=5, r_min=0.5, engine="vmap"))
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(vmap.ids))
    assert idx.search(q, req).stats.engine == "fused"


# ---------------------------------------------------------------------------
# Protocol conformance (acceptance criterion)
# ---------------------------------------------------------------------------

def test_both_indexes_satisfy_the_protocol(static_idx):
    idx, data, rng = static_idx
    assert isinstance(idx, AnnIndex)
    assert not isinstance(idx, MutableAnnIndex)
    assert as_ann_index(idx) is idx

    sidx = repro.api.build(
        jnp.asarray(data), jax.random.key(1),
        IndexSpec(kind="streaming", K=4, L=4, c=1.5, beta_override=0.1,
                  Nr=32, leaf_size=16, delta_capacity=32))
    assert isinstance(sidx, AnnIndex)
    assert isinstance(sidx, MutableAnnIndex)
    assert as_ann_index(sidx) is sidx


def test_legacy_adapter_wraps_query_only_objects(static_idx):
    idx, data, rng = static_idx

    class Legacy:
        def __init__(self, inner):
            self._inner = inner

        def query(self, queries, k=10):
            # A pre-protocol surface; implemented on the typed search so
            # the suite stays clean under -W error::DeprecationWarning.
            return self._inner.search(queries, SearchRequest(k=k)).raw

    adapted = as_ann_index(Legacy(idx))
    assert isinstance(adapted, LegacyIndexAdapter)
    assert not adapted.supports_n_active
    q = jnp.asarray(make_queries_near(data, rng, 4))
    res = adapted.search(q, SearchRequest(k=5, n_active=2))  # dropped, ok
    assert res.ids.shape == (4, 5)
    with pytest.raises(TypeError, match="no query"):
        as_ann_index(object())


def test_build_rejects_mismatched_kind(static_idx):
    idx, data, rng = static_idx
    from repro.streaming import StreamingDETLSH
    with pytest.raises(ValueError, match="static"):
        DETLSH.from_spec(jnp.asarray(data), jax.random.key(0),
                         IndexSpec(kind="streaming"))
    with pytest.raises(ValueError, match="streaming"):
        StreamingDETLSH.from_spec(jnp.asarray(data), jax.random.key(0),
                                  IndexSpec(kind="static"))


# ---------------------------------------------------------------------------
# r_min caching (satellite: one estimate per (index, k), not per batch)
# ---------------------------------------------------------------------------

def test_r_min_estimated_once_per_k(static_idx, monkeypatch):
    import repro.core as core
    idx, data, rng = static_idx
    idx._r_min_cache.clear()
    counter = {"n": 0}
    real = core.estimate_r_min

    def counting(*a, **kw):
        counter["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(core, "estimate_r_min", counting)
    q = jnp.asarray(make_queries_near(data, rng, 4))
    r1 = idx.search(q, SearchRequest(k=5))
    r2 = idx.search(q, SearchRequest(k=5))
    assert counter["n"] == 1               # second batch reuses the cache
    assert r1.stats.r_min == r2.stats.r_min == idx.r_min_for(5)
    assert not r1.stats.r_min_cached       # first search pays the estimate
    assert r2.stats.r_min_cached           # ...and the second is a hit
    idx.search(q, SearchRequest(k=9))
    assert counter["n"] == 2               # distinct k => distinct estimate
    res = idx.search(q, SearchRequest(k=5, r_min=2.5))
    assert res.stats.r_min == 2.5 and not res.stats.r_min_cached
    assert counter["n"] == 2               # explicit r_min bypasses


def test_streaming_r_min_cache_invalidated_by_mutation(static_idx):
    idx, data, rng = static_idx
    sidx = repro.api.build(
        jnp.asarray(data[:256]), jax.random.key(1),
        IndexSpec(kind="streaming", K=4, L=4, c=1.5, beta_override=0.1,
                  Nr=32, leaf_size=16, delta_capacity=32))
    q = jnp.asarray(make_queries_near(data, rng, 4))
    sidx.search(q, SearchRequest(k=5))
    tag0, cache0 = sidx._rmin_cache
    assert 5 in cache0
    sidx.upsert(make_clustered(rng, 3, D))
    sidx.search(q, SearchRequest(k=5))
    tag1, cache1 = sidx._rmin_cache
    assert tag1 != tag0                    # mutation invalidated the cache


# ---------------------------------------------------------------------------
# Deprecation shims + engine default from the spec
# ---------------------------------------------------------------------------

def test_query_shim_warns_and_matches_search(static_idx):
    idx, data, rng = static_idx
    q = jnp.asarray(make_queries_near(data, rng, 8))
    with pytest.warns(DeprecationWarning, match="search"):
        old = idx.query(q, k=5)
    new = idx.search(q, SearchRequest(k=5))
    np.testing.assert_array_equal(np.asarray(old.ids), np.asarray(new.ids))
    np.testing.assert_array_equal(np.asarray(old.dists),
                                  np.asarray(new.dists))


def test_streaming_query_shim_warns(static_idx):
    idx, data, rng = static_idx
    sidx = repro.api.build(
        jnp.asarray(data[:256]), jax.random.key(1),
        IndexSpec(kind="streaming", K=4, L=4, c=1.5, beta_override=0.1,
                  Nr=32, leaf_size=16, delta_capacity=32))
    q = jnp.asarray(make_queries_near(data, rng, 4))
    with pytest.warns(DeprecationWarning, match="search"):
        old = sidx.query(q, k=5)
    new = sidx.search(q, SearchRequest(k=5))
    np.testing.assert_array_equal(np.asarray(old.ids), np.asarray(new.ids))


def test_spec_engine_is_the_search_default(static_idx):
    idx, data, rng = static_idx
    q = jnp.asarray(make_queries_near(data, rng, 16))
    import dataclasses
    vmap_idx = dataclasses.replace(idx)
    vmap_idx.spec = dataclasses.replace(idx.spec, engine="vmap")
    res = vmap_idx.search(q, SearchRequest(k=5))
    assert res.stats.engine == "vmap"      # spec default, batch >= 8
    res = vmap_idx.search(q, SearchRequest(k=5, engine="fused"))
    assert res.stats.engine == "fused"     # request overrides spec
    res = idx.search(q, SearchRequest(k=5))
    assert res.stats.engine == "fused"     # plain auto at batch 16
