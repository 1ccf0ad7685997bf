"""DET-LSH / PDET-LSH — the paper's primary contribution, in JAX.

High-level API (see ``repro.api`` for the protocol surface)::

    import repro
    spec = repro.api.IndexSpec(kind="static", K=16, c=1.5, L=4)
    index = repro.api.build(data, key, spec)
    res = index.search(queries, repro.api.SearchRequest(k=50))
    index.save("snap/"); index = repro.api.load("snap/")

Submodules: theory, hashing, encoding, detree, query, distributed,
det_attention.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax
import numpy as np

from typing import Optional

from repro import tracing
from repro.core.theory import LSHParams, derive_params, SUCCESS_PROBABILITY
from repro.core import hashing, encoding, detree
from repro.core.detree import DEForest, build_forest
from repro.core.query import (FusedPlan, QueryConfig, QueryResult,
                              knn_query_batch, make_fused_plan)


def estimate_r_min(data: jax.Array, queries: jax.Array, k: int,
                   c: float, *, sample: int = 2048) -> float:
    """Pick the initial search radius (paper §V-B1, following PM-LSH [9]).

    Heuristic realization of the "magic r_min": estimate the k-NN distance
    scale on a subsample and start one c-step below it, so the first rounds
    neither trivially satisfy T1 nor waste many enlargements.
    """
    ns = min(sample, data.shape[0])
    nq = min(64, queries.shape[0])
    sub = np.asarray(data[:ns])
    qs = np.asarray(queries[:nq])
    d2 = ((qs[:, None, :] - sub[None, :, :]) ** 2).sum(-1)
    kth = np.sqrt(np.partition(d2, min(k, ns - 1), axis=1)[:, min(k, ns - 1)])
    r = float(np.median(kth))
    return max(r / (c * c), 1e-6)


@dataclasses.dataclass
class DETLSH:
    """A built DET-LSH index (single shard; see core.distributed for pods).

    Satisfies the ``repro.api.AnnIndex`` protocol: ``search`` is the typed
    query surface, ``save``/``repro.api.load`` the snapshot round-trip.
    """

    params: LSHParams
    A: jax.Array           # (d, L*K) projection matrix
    forest: DEForest
    data: jax.Array        # (n, d) — kept resident for exact rerank (paper §VI-C4)
    # The IndexSpec this index was built from (None for direct .build calls).
    spec: Optional["object"] = dataclasses.field(
        default=None, repr=False, compare=False)
    # Fused-engine constants (code-sorted points + inverse permutations),
    # built lazily once per index and reused across query batches.
    _plan: Optional[FusedPlan] = dataclasses.field(
        default=None, repr=False, compare=False)
    # Per-k cached r_min estimates: estimate_r_min is an O(nq*sample*d)
    # host-side numpy pass — once per (index, k), not once per batch.
    _r_min_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @classmethod
    def build(cls, data: jax.Array, key: jax.Array,
              params: LSHParams | None = None, *,
              Nr: int = encoding.DEFAULT_NR, leaf_size: int = 64,
              breakpoint_method: str = "sample_sort",
              project_impl: str = "auto",
              encode_impl: str = "auto",
              build_impl: str = "auto",
              build_chunk: int = 512) -> "DETLSH":
        """One-shot static build (Alg. 1 + 2).  ``build_impl`` /
        ``build_chunk`` select the fused single-sort build pipeline and its
        row-chunk size ('reference' = the seed per-tree double-argsort
        path; both produce bit-identical forests — docs/DESIGN.md §8)."""
        params = params or derive_params()
        d = data.shape[1]
        kp, kb = jax.random.split(key)
        A = hashing.sample_projections(kp, d, params.K, params.L)
        proj = hashing.project(data, A, impl=project_impl)
        forest = build_forest(proj, params.K, params.L, Nr=Nr,
                              leaf_size=leaf_size,
                              breakpoint_method=breakpoint_method, key=kb,
                              encode_impl=encode_impl,
                              build_impl=build_impl, build_chunk=build_chunk)
        return cls(params=params, A=A, forest=forest, data=data)

    @classmethod
    def from_spec(cls, data: jax.Array, key: jax.Array,
                  spec) -> "DETLSH":
        """Build from one declarative ``repro.api.IndexSpec``."""
        if spec.kind != "static":
            raise ValueError(f"DETLSH.from_spec needs kind='static', got "
                             f"{spec.kind!r} (use repro.api.build)")
        idx = cls.build(data, key, spec.derive_params(), Nr=spec.Nr,
                        leaf_size=spec.leaf_size,
                        breakpoint_method=spec.breakpoint_method,
                        project_impl=spec.project_impl,
                        encode_impl=spec.encode_impl,
                        build_impl=spec.build_impl,
                        build_chunk=spec.build_chunk)
        idx.spec = spec
        return idx

    @property
    def n_points(self) -> int:
        return int(self.data.shape[0])

    def fused_plan(self) -> FusedPlan:
        if self._plan is None:
            self._plan = make_fused_plan(self.data, self.forest)
        return self._plan

    def r_min_for(self, k: int, queries: jax.Array | None = None) -> float:
        """Cached per-(index, k) starting radius.

        ``estimate_r_min`` is an O(nq·sample·d) host-side numpy pass; it
        now runs once per (index, k) — on the first ``r_min=None`` search,
        estimated from that batch's queries (the paper's PM-LSH heuristic)
        — and every later search with the same k reuses the cached value
        for free.  With no queries yet seen for this k, data rows stand in
        as probes.  Any estimate only shifts the starting radius; the
        c²-guarantee holds for every r_min (docs/DESIGN.md §6).
        """
        if k not in self._r_min_cache:
            probes = (queries if queries is not None
                      else self.data[: min(64, self.data.shape[0])])
            self._r_min_cache[k] = estimate_r_min(self.data, probes, k,
                                                  self.params.c)
        return self._r_min_cache[k]

    def search(self, queries: jax.Array, request=None):
        """Typed batched search (``repro.api.SearchRequest`` in,
        ``repro.api.SearchResult`` out).  Trace-compatible when the
        request carries an explicit ``r_min``."""
        from repro.api import registry
        from repro.api.request import SearchRequest, SearchResult, \
            SearchStats
        req = request or SearchRequest()
        r_min, cached = req.r_min, False
        if r_min is None:
            cached = req.k in self._r_min_cache    # hit vs first estimate
            probes = None
            if not cached:
                # Zero-vector pad lanes must not skew the estimate (n_active
                # == 0 keeps the full batch: no real lanes to probe).
                probes = queries[: req.n_active] if req.n_active else queries
            r_min = self.r_min_for(req.k, probes)
        spec = self.spec
        default_engine = spec.engine if spec is not None else "auto"
        cfg = req.to_query_config(
            default_engine=default_engine, r_min=r_min,
            block_q=spec.block_q if spec is not None else 8,
            block_l=spec.block_l if spec is not None else 8,
            default_probe_depth=spec.probe_depth if spec is not None else 0)
        batch = queries.shape[0]
        engine = registry.resolve_engine(cfg.engine, mode=cfg.mode,
                                         batch=batch)
        plan = self.fused_plan() if engine == "fused" else None
        with tracing.span("detlsh.search.dispatch", batch=batch,
                          n_active=(batch if req.n_active is None
                                    else int(req.n_active)),
                          engine=engine):
            res = knn_query_batch(self.data, self.forest, self.A,
                                  self.params, queries, cfg, plan=plan,
                                  n_active=req.n_active)
        return SearchResult(
            ids=res.ids, dists=res.dists,
            stats=SearchStats(engine=engine, r_min=float(r_min),
                              r_min_cached=cached, rounds=res.rounds,
                              n_candidates=res.n_candidates,
                              final_r=res.final_r,
                              probed_leaves=res.probed_leaves,
                              probe_candidates=res.probe_candidates),
            raw=res)

    def query(self, queries: jax.Array, k: int = 50, *,
              r_min: float | None = None, M: int = 8,
              mode: str = "leaf", max_rounds: int = 48,
              engine: str = "auto",
              n_active: int | None = None) -> QueryResult:
        """Deprecated kwarg surface — use ``search(queries,
        repro.api.SearchRequest(...))``.  Kept as a thin shim for the
        seed-era callers; returns the engine-level ``QueryResult``."""
        warnings.warn(
            "DETLSH.query(**kwargs) is deprecated; use "
            "DETLSH.search(queries, repro.api.SearchRequest(...))",
            DeprecationWarning, stacklevel=2)
        from repro.api.request import SearchRequest
        req = SearchRequest(k=k, r_min=r_min, M=M, mode=mode,
                            max_rounds=max_rounds, engine=engine,
                            n_active=n_active)
        return self.search(queries, req).raw

    def save(self, path) -> None:
        """Write a versioned snapshot directory (``repro.api.load``)."""
        from repro.api import persist
        persist.save_static(self, path)

    def index_size_bytes(self) -> int:
        return self.forest.size_bytes() + self.A.size * 4


__all__ = [
    "DETLSH", "DEForest", "FusedPlan", "LSHParams", "QueryConfig",
    "QueryResult", "derive_params", "build_forest", "knn_query_batch",
    "make_fused_plan", "estimate_r_min", "SUCCESS_PROBABILITY",
]
