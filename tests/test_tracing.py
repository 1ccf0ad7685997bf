"""Program spans (``repro.tracing``) and the named scopes of the fused
search and the build.

Spans are read back from a real profiler session (``jax.profiler``'s
``.xplane.pb``), as the benchmark's trace reduction reads them; the scopes
from the lowered module's locations.  A scope may change op metadata
only: the module without debug info and the answers stay identical.
"""

import contextlib
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import tracing
from repro.api import SearchRequest
from repro.core import DETLSH, derive_params, detree, estimate_r_min, query
from repro.core.query import QueryConfig, fused_query_batch, make_fused_plan
from repro.serving import Answer, ServingRuntime
from tests.conftest import make_clustered


def _spans(trace_dir):
    """``detlsh.*`` host events of the newest trace: (name, start_ns,
    end_ns, args), in start order."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tracing.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


@contextlib.contextmanager
def _profiled(tmp_path):
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        yield d
    finally:
        jax.profiler.stop_trace()


def _fresh_jit():
    # a new function object: its first call traces and compiles
    return jax.jit(lambda x: jnp.cos(x) * 3.0 + 1.0)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_spans_nest_and_args_land_on_their_span(tmp_path):
    with _profiled(tmp_path) as d:
        with tracing.span("detlsh.test.outer", a=1):
            with tracing.span("detlsh.test.inner", b="x") as inner:
                inner.set(c=2.5, flag=True)
    spans = _spans(d)
    assert [s[0] for s in spans] == ["detlsh.test.outer", "detlsh.test.inner"]
    (_, o0, o1, oargs), (_, i0, i1, iargs) = spans
    assert o0 <= i0 and i1 <= o1                 # inner lies inside outer
    assert oargs["a"] == 1 and "b" not in oargs and "c" not in oargs
    assert iargs["b"] == "x" and iargs["c"] == 2.5 and iargs["flag"] == 1
    assert "a" not in iargs
    for args in (oargs, iargs):                  # every compile arg written
        assert set(tracing.ARGS) <= set(args)


def test_compiles_are_charged_to_the_innermost_span(tmp_path):
    with _profiled(tmp_path) as d:
        _fresh_jit()(jnp.ones(7)).block_until_ready()   # outside any span
        with tracing.span("detlsh.test.parent"):
            with tracing.span("detlsh.test.child"):
                _fresh_jit()(jnp.ones(5)).block_until_ready()
        with tracing.span("detlsh.test.after"):
            pass
    args = {name: a for name, _, _, a in _spans(d)}
    child = args["detlsh.test.child"]
    assert child["traces"] >= 1 and child["compiles"] >= 1
    assert child["trace_ms"] > 0 and child["lower_ms"] > 0
    assert child["compile_ms"] > 0
    for name in ("detlsh.test.parent", "detlsh.test.after"):
        assert all(args[name][k] == 0 for k in tracing.ARGS), args[name]


def test_gc_time_is_charged_to_the_open_span(tmp_path):
    import gc
    with _profiled(tmp_path) as d:
        with tracing.span("detlsh.test.gc"):
            gc.collect()
    [(_, _, _, args)] = _spans(d)
    assert args["gc_ms"] > 0


def test_span_without_profiler_records_nothing():
    assert not TraceAnnotation.is_enabled()
    with tracing.span("detlsh.test.off", a=1) as s:
        s.set(b=2)
        _fresh_jit()(jnp.ones(3)).block_until_ready()
        assert tracing._stack()[-1] is s
    assert s.args is None and tracing._stack() == []


def test_span_name_needs_the_prefix():
    with pytest.raises(ValueError, match="detlsh"):
        tracing.span("search.dispatch")


# ---------------------------------------------------------------------------
# Spans in the program
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(11)
    data = make_clustered(rng, 2048, 16)
    queries = make_clustered(rng, 16, 16)
    p = derive_params(K=4, c=1.5, L=4, beta_override=0.1)
    idx = DETLSH.build(jnp.asarray(data), jax.random.key(5), p,
                       leaf_size=32)
    r0 = estimate_r_min(idx.data, jnp.asarray(queries), 10, p.c)
    return idx, queries, r0


def test_search_dispatch_span_carries_batch_and_engine(built, tmp_path):
    idx, queries, r0 = built
    with _profiled(tmp_path) as d:
        idx.search(jnp.asarray(queries),
                   SearchRequest(k=10, r_min=r0, n_active=12, engine="fused"))
    [(name, _, _, args)] = _spans(d)
    assert name == "detlsh.search.dispatch"
    assert args["batch"] == 16 and args["n_active"] == 12
    assert args["engine"] == "fused"


def test_warm_dispatch_span_reads_no_compile_work(built, tmp_path):
    """A warm search runs its compiled program: the dispatch span, which
    ``search.lower_ms_per_batch`` reads, charges it no trace, lowering or
    compile, whatever the request's radius or active lanes."""
    idx, queries, r0 = built
    q = jnp.asarray(queries)
    idx.search(q, SearchRequest(k=10, r_min=r0, engine="fused"))
    with _profiled(tmp_path) as d:
        for scale, n_active in ((1.0, 16), (2.0, 5), (0.5, 9)):
            idx.search(q, SearchRequest(k=10, r_min=r0 * scale,
                                        n_active=n_active, engine="fused"))
    spans = _spans(d)
    assert [s[0] for s in spans] == ["detlsh.search.dispatch"] * 3
    for _, _, _, args in spans:
        assert args["traces"] == args["compiles"] == 0, args
        assert args["trace_ms"] == args["lower_ms"] == 0, args


def test_serve_batch_span_sums_each_requests_queue_wait(built, tmp_path):
    idx, queries, r0 = built
    ticks = iter(np.arange(100.0, 200.0, 0.25))
    rt = ServingRuntime(idx, k=10, max_batch=8, pad_to=8,
                        clock=lambda: float(next(ticks)),
                        request=SearchRequest(k=10, r_min=r0))
    arrivals = [99.0, 99.5, 99.75]
    rids = [rt.submit(q, arrival=a) for q, a in zip(queries, arrivals)]
    with _profiled(tmp_path) as d:
        rt.flush()
    spans = _spans(d)
    assert [s[0] for s in spans] == ["detlsh.serve.batch",
                                     "detlsh.search.dispatch"]
    args = spans[0][3]
    # batch start 100.0: waits 1000, 500, 250 ms
    assert args["batch_id"] == 0 and args["queries"] == 3
    assert args["pad"] == 5 and args["bucket"] == 8
    assert args["degraded"] == 0
    assert args["wait_ms_sum"] == 1750.0 and args["wait_ms_max"] == 1000.0
    waits = [rt.outcomes[r].queue_ms for r in rids]
    assert waits == [1000.0, 500.0, 250.0]
    assert all(isinstance(rt.outcomes[r], Answer) for r in rids)


# ---------------------------------------------------------------------------
# Named scopes
# ---------------------------------------------------------------------------

def _fused_lowered(idx, queries, r0):
    cfg = QueryConfig(k=10, M=8, r_min=r0, engine="fused")
    plan = make_fused_plan(idx.data, idx.forest)
    return jax.jit(lambda q: fused_query_batch(
        idx.data, idx.forest, idx.A, idx.params, q, cfg, plan)).lower(
        jnp.asarray(queries))


def _assemble(n=100, leaf_size=16):
    # a fresh function object: traced anew, with the scopes as they are now
    return jax.jit(lambda p, c, o: detree.assemble_sorted_forest(
        p, c, o, n=n, leaf_size=leaf_size))


def _assemble_lowered(L=2, n=100, K=3):
    return _assemble(n).lower(
        jax.ShapeDtypeStruct((L, n, K), jnp.float32),
        jax.ShapeDtypeStruct((L, n, K), jnp.uint8),
        jax.ShapeDtypeStruct((L, n), jnp.int32))


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    # the search's compiled program is traced anew inside and after
    programs = (query._fused_program, query._vmap_program)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        for program in programs:
            program.clear_cache()
        try:
            yield
        finally:
            for program in programs:
                program.clear_cache()


def test_fold_scope_is_in_the_fused_search(built):
    idx, queries, r0 = built
    text = _fused_lowered(idx, queries, r0).as_text(debug_info=True)
    assert "/fold/" in text
    assert text.count("/fold/") >= idx.params.L     # each tree's gather


def test_assemble_scope_is_in_the_build():
    text = _assemble_lowered().as_text(debug_info=True)
    assert "/assemble/" in text


def test_scopes_change_only_metadata(built, monkeypatch):
    idx, queries, r0 = built
    fused = _fused_lowered(idx, queries, r0).as_text()
    assemble = _assemble_lowered().as_text()
    with _no_scopes(monkeypatch):
        assert "/fold/" not in _fused_lowered(idx, queries, r0).as_text(
            debug_info=True)
        assert "/assemble/" not in _assemble_lowered().as_text(
            debug_info=True)
        assert _fused_lowered(idx, queries, r0).as_text() == fused
        assert _assemble_lowered().as_text() == assemble


def test_answers_are_bit_identical_without_scopes(built, monkeypatch):
    idx, queries, r0 = built
    req = SearchRequest(k=10, r_min=r0, engine="fused")
    rng = np.random.default_rng(3)
    L, n, K = 4, 1000, 4
    order = np.argsort(rng.random((L, n)), axis=1, kind="stable")
    layout = (jnp.asarray(rng.standard_normal((L, n, K)), jnp.float32),
              jnp.asarray(rng.integers(0, 256, (L, n, K)), jnp.uint8),
              jnp.asarray(order, jnp.int32))
    with_scopes = idx.search(jnp.asarray(queries), req)
    forest = _assemble(n, 32)(*layout)
    with _no_scopes(monkeypatch):
        without = idx.search(jnp.asarray(queries), req)
        forest_plain = _assemble(n, 32)(*layout)
    for a, b in ((with_scopes.ids, without.ids),
                 (with_scopes.dists, without.dists),
                 (with_scopes.stats.n_candidates,
                  without.stats.n_candidates)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert forest.keys() == forest_plain.keys()
    for name in forest:
        np.testing.assert_array_equal(np.asarray(forest[name]),
                                      np.asarray(forest_plain[name]))


def test_compile_cache_key_is_salted_with_the_scopes():
    """An executable cached from source without the scopes must not be
    loaded under them: the cache key carries ``tracing.SCOPES``."""
    from jax._src import cache_key
    assert cache_key.custom_hook().endswith(tracing.SCOPES)
    assert "fold" in tracing.SCOPES and "assemble" in tracing.SCOPES
