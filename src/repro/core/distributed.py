"""PDET-LSH: the multi-pod distributed runtime (paper §IV, Alg. 6/7/8).

CPU-thread parallelism -> TPU SPMD mapping (DESIGN.md §2):

  * Alg. 6 (parallel dynamic encoding, dimension-partitioned): breakpoint
    selection runs as *distributed histogram refinement* — per-shard
    histograms are ``psum``-reduced so every device derives the identical,
    globally equi-depth breakpoints.  log2(N_r) rounds of small (D, N_r)
    collectives replace the paper's per-worker QuickSelect.
  * Alg. 7 (parallel index construction, data-partitioned): each device
    builds a complete DE-Forest over its own shard of the dataset.  No
    synchronization at all (the paper needs a barrier + subtree hand-off).
  * Alg. 8 + §IV-C (parallel query): queries are replicated; every device
    range-queries its local forest and reranks its local candidates
    (rerank gathers are shard-local — the dataset is sharded *with* the
    index).  Termination conditions T1/T2 of Alg. 5 are evaluated on
    ``psum``-ed global counts, so all devices advance the radius in
    lockstep and the termination logic — hence Theorem 3 — is preserved.
    The final top-k is an ``all_gather`` of per-shard top-k + a merge.

Determinism/equivalence: ``serial_reference_*`` run the identical sharded
algorithm as plain vmapped code on one device; tests assert the shard_map
version returns exactly the same ids/distances (the PDET == DET claim,
Fig. 20/21).

Two sharded runtimes live here (DESIGN.md §7):

  * ``PDETLSH`` / ``build_pdet`` — the *structure-partitioned* runtime
    above (per-shard forests, work-partitioned build).  Kept for the
    parallel-build benchmarks and the serial-reference equivalence tests.
  * ``PDETIndex`` — the *layout-partitioned* runtime behind ``repro.api``:
    the one global forest sharded across the mesh, queried by the fused
    round with an exact ``pmin`` merge, making PDET == DET a bit-identical
    API contract for any device count.  This is the index ``repro.api.build``
    returns for an ``IndexSpec`` with a ``placement`` and the ``pdet``
    entry in the engine registry.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api import registry as engine_registry
from jax import shard_map

from repro.core import encoding as enc
from repro.core import hashing
from repro.core.hashing import project_query
from repro.core.detree import DEForest, build_tree, fused_forest_arrays
from repro.core.query import (FusedPlan, QueryConfig, QueryResult,
                              _merge_candidates, fused_round_update,
                              fused_topk, knn_query_batch)
from repro.core.theory import LSHParams


# ---------------------------------------------------------------------------
# Distributed breakpoint selection (Alg. 6 analogue)
# ---------------------------------------------------------------------------

def distributed_breakpoints(proj_local: jax.Array, n_global: int,
                            Nr: int, rounds: int,
                            axes: Sequence[str] | None) -> jax.Array:
    """Globally equi-depth breakpoints over data sharded on ``axes``.

    proj_local: (n_local, D).  Inside shard_map, ``axes`` are the mesh axes
    the data is sharded over; pass None for the serial reference.
    """
    def pmin(x):
        return jax.lax.pmin(x, axes) if axes else x

    def pmax(x):
        return jax.lax.pmax(x, axes) if axes else x

    def psum(x):
        return jax.lax.psum(x, axes) if axes else x

    lo = pmin(jnp.min(proj_local, axis=0))
    hi = pmax(jnp.max(proj_local, axis=0))
    t = jnp.arange(Nr + 1, dtype=jnp.float32) / Nr
    edges = lo[:, None] + (hi - lo)[:, None] * t[None, :]

    def body(_, edges):
        counts = psum(enc.histogram_counts(proj_local, edges))
        return enc.refine_breakpoints_from_counts(edges, counts, n_global)

    return jax.lax.fori_loop(0, rounds, body, edges)


# ---------------------------------------------------------------------------
# Shard-local build (Alg. 7 analogue)
# ---------------------------------------------------------------------------

def _build_local_forest(data_local: jax.Array, A: jax.Array, K: int, L: int,
                        Nr: int, leaf_size: int, bp_rounds: int,
                        n_global: int,
                        axes: Sequence[str] | None) -> DEForest:
    """Per-shard forest over the local data (Alg. 7), through the shared
    fused single-sort pipeline (encode + key-pack kernel, one stable sort
    for all L trees — docs/DESIGN.md §8); only the breakpoints are global
    (psum'd histogram refinement).  Bit-identical to the per-tree reference
    builder, which ``serial_reference_build`` still uses as the
    cross-check (tests/test_distributed.py, tests/test_build_fused.py)."""
    n_local = data_local.shape[0]
    proj = hashing.project(data_local, A)
    bp_all = distributed_breakpoints(proj, n_global, Nr, bp_rounds, axes)
    parts = fused_forest_arrays(proj, bp_all, K=K, L=L, leaf_size=leaf_size)
    return DEForest(n=n_local, leaf_size=leaf_size,
                    breakpoints=bp_all.reshape(L, K, Nr + 1), **parts)


# ---------------------------------------------------------------------------
# Shard-local query with global termination (Alg. 5 + Alg. 8 analogue)
# ---------------------------------------------------------------------------

def _knn_local(data_local: jax.Array, forest: DEForest, A: jax.Array,
               params: LSHParams, q: jax.Array, cfg: QueryConfig,
               n_global: int, shard_offset: jax.Array,
               axes: Sequence[str] | None):
    """One query against the local shard, radius loop in global lockstep.

    Returns per-shard top-k (ids globalized via shard_offset) — caller
    all_gathers and merges.
    """
    from repro.core.query import range_query_round, exact_distances

    def psum(x):
        return jax.lax.psum(x, axes) if axes else x

    n_local = data_local.shape[0]
    K, L = params.K, params.L
    M = min(cfg.M, forest.n_leaves)
    round_cap = L * M * forest.leaf_size
    # Local buffer: the global termination threshold can be met by any
    # distribution of candidates over shards, so each shard must be able to
    # hold everything it could contribute before termination.
    cap = min(int(params.beta * n_global) + cfg.k + round_cap,
              n_local + round_cap)
    thresh = jnp.asarray(params.beta * n_global + cfg.k, jnp.float32)
    q_proj = project_query(q, A).reshape(L, K)

    def cond(state):
        rnd, r, ids, d, done = state
        return (~done) & (rnd < cfg.max_rounds)

    def body(state):
        rnd, r, ids, d, done = state
        new_ids, ok = range_query_round(forest, q_proj, params.epsilon * r,
                                        cfg.M, mode=cfg.mode)
        new_d = exact_distances(data_local, q, new_ids, ok)
        new_ids = jnp.where(ok, new_ids, n_local)
        ids, d, count_local = _merge_candidates(n_local, ids, d, new_ids,
                                                new_d)
        count = psum(count_local.astype(jnp.float32))            # global |S|
        within_local = jnp.sum(d <= params.c * r).astype(jnp.float32)
        within = psum(within_local)                              # global T2
        done = (count >= thresh) | (within >= cfg.k)
        r = jnp.where(done, r, r * params.c)
        return rnd + 1, r, ids, d, done

    state0 = (jnp.asarray(0, jnp.int32), jnp.asarray(cfg.r_min, jnp.float32),
              jnp.full((cap,), n_local, jnp.int32),
              jnp.full((cap,), jnp.inf), jnp.asarray(False))
    rnd, r, ids, d, done = jax.lax.while_loop(cond, body, state0)

    kk = min(cfg.k, cap)
    negd, sel = jax.lax.top_k(-d, kk)
    local_ids = ids[sel]
    gids = jnp.where(local_ids < n_local, local_ids + shard_offset,
                     n_global).astype(jnp.int32)
    return gids, -negd, rnd


def _merge_global_topk(gids: jax.Array, gdists: jax.Array, k: int,
                       axes: Sequence[str] | None):
    """all_gather per-shard top-k and take the global top-k."""
    if axes:
        gids = jax.lax.all_gather(gids, axes, tiled=True)
        gdists = jax.lax.all_gather(gdists, axes, tiled=True)
    negd, sel = jax.lax.top_k(-gdists, k)
    return gids[sel], -negd


# ---------------------------------------------------------------------------
# Public API: shard_map-based build & query over a mesh
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PDETLSH:
    """A PDET-LSH index sharded over mesh ``axes`` (data-parallel)."""

    params: LSHParams
    A: jax.Array
    forest: DEForest          # arrays sharded on their n/leaf axes
    data: jax.Array           # (n, d) sharded on axis 0
    mesh: Mesh
    axes: tuple[str, ...]
    n_global: int

    def query(self, queries: jax.Array, k: int = 50, *,
              r_min: float | None = None, M: int = 8,
              mode: str = "leaf", max_rounds: int = 48):
        if r_min is None:
            from repro.core import estimate_r_min
            r_min = estimate_r_min(
                jax.device_get(self.data)[: min(2048, self.n_global)],
                queries, k, self.params.c)
        cfg = QueryConfig(k=k, M=M, r_min=r_min, mode=mode,
                          max_rounds=max_rounds)
        return query_pdet(self, queries, cfg)


def _shard_spec(mesh: Mesh, axes: tuple[str, ...]):
    data_p = P(axes)
    forest_p = DEForest(
        point_ids=P(None, axes), proj_sorted=P(None, axes, None),
        codes_sorted=P(None, axes, None), valid=P(None, axes),
        leaf_lo=P(None, axes, None), leaf_hi=P(None, axes, None),
        leaf_valid=P(None, axes), breakpoints=P(),
        n=0, leaf_size=0)
    return data_p, forest_p


def build_pdet(data: jax.Array, key: jax.Array, params: LSHParams,
               mesh: Mesh, axes: tuple[str, ...] = ("data",), *,
               Nr: int = enc.DEFAULT_NR, leaf_size: int = 64,
               bp_rounds: int = 8) -> PDETLSH:
    """Build the distributed index.  ``data`` (n, d); n divisible by the
    product of mesh axis sizes in ``axes`` (pad upstream)."""
    n, d = data.shape
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    assert n % n_shards == 0, (n, n_shards)
    A = hashing.sample_projections(key, d, params.K, params.L)

    data_p, forest_p = _shard_spec(mesh, axes)
    forest_specs = dict(point_ids=P(None, axes),
                        proj_sorted=P(None, axes, None),
                        codes_sorted=P(None, axes, None),
                        valid=P(None, axes),
                        leaf_lo=P(None, axes, None),
                        leaf_hi=P(None, axes, None),
                        leaf_valid=P(None, axes),
                        breakpoints=P())

    def build(data_local, A):
        f = _build_local_forest(data_local, A, params.K, params.L, Nr,
                                leaf_size, bp_rounds, n, axes)
        return dict(point_ids=f.point_ids, proj_sorted=f.proj_sorted,
                    codes_sorted=f.codes_sorted, valid=f.valid,
                    leaf_lo=f.leaf_lo, leaf_hi=f.leaf_hi,
                    leaf_valid=f.leaf_valid, breakpoints=f.breakpoints)

    built = shard_map(
        build, mesh=mesh, in_specs=(data_p, P()),
        out_specs=forest_specs, check_vma=False)(data, A)
    n_local = n // n_shards
    forest = DEForest(n=n_local, leaf_size=leaf_size, **built)
    return PDETLSH(params=params, A=A, forest=forest, data=data, mesh=mesh,
                   axes=tuple(axes), n_global=n)


def query_pdet(index: PDETLSH, queries: jax.Array, cfg: QueryConfig):
    """Batched distributed c^2-k-ANN (queries replicated; Theorem 3 path)."""
    mesh, axes = index.mesh, index.axes
    n_global = index.n_global
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_local = n_global // n_shards

    data_p, _ = _shard_spec(mesh, axes)
    forest_specs = DEForest(
        point_ids=P(None, axes), proj_sorted=P(None, axes, None),
        codes_sorted=P(None, axes, None), valid=P(None, axes),
        leaf_lo=P(None, axes, None), leaf_hi=P(None, axes, None),
        leaf_valid=P(None, axes), breakpoints=P(), n=index.forest.n,
        leaf_size=index.forest.leaf_size)

    def run(data_local, forest, A, queries):
        # shard offset from the mesh position along the data axes
        # (row-major over ``axes`` — matches jnp.reshape sharding order)
        idx = jnp.asarray(0, jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        offset = idx * n_local

        def one(q):
            gids, gd, rnd = _knn_local(data_local, forest, A, index.params,
                                       q, cfg, n_global, offset, axes)
            mids, md = _merge_global_topk(gids, gd, cfg.k, axes)
            return mids, md, rnd

        return jax.vmap(one)(queries)

    in_specs = (data_p, forest_specs, P(), P())
    out_specs = (P(), P(), P())
    gids, gdists, rounds = shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(index.data, index.forest, index.A, queries)
    return gids, gdists, rounds


# ---------------------------------------------------------------------------
# Serial reference: identical sharded semantics on one device (for tests)
# ---------------------------------------------------------------------------

def serial_reference_build(data: jax.Array, key: jax.Array,
                           params: LSHParams, n_shards: int, *,
                           Nr: int = enc.DEFAULT_NR, leaf_size: int = 64,
                           bp_rounds: int = 8):
    """vmap-over-shards build with summed (\"psum\") histogram counts."""
    from repro.core.detree import check_nr
    check_nr(Nr)
    n, d = data.shape
    assert n % n_shards == 0
    A = hashing.sample_projections(key, d, params.K, params.L)
    shards = data.reshape(n_shards, n // n_shards, d)
    proj = jax.vmap(lambda x: hashing.project(x, A))(shards)

    # distributed_breakpoints with psum == sum over the shard axis
    lo = jnp.min(proj, axis=(0, 1))
    hi = jnp.max(proj, axis=(0, 1))
    t = jnp.arange(Nr + 1, dtype=jnp.float32) / Nr
    edges = lo[:, None] + (hi - lo)[:, None] * t[None, :]
    for _ in range(bp_rounds):
        counts = sum(enc.histogram_counts(proj[s], edges)
                     for s in range(n_shards))
        edges = enc.refine_breakpoints_from_counts(edges, counts, n)

    K, L = params.K, params.L

    def build_one(proj_local):
        codes = enc.encode(proj_local, edges)
        nl = proj_local.shape[0]
        proj_t = proj_local.reshape(nl, L, K).transpose(1, 0, 2)
        codes_t = codes.reshape(nl, L, K).transpose(1, 0, 2)
        bp_t = edges.reshape(L, K, Nr + 1)
        return jax.vmap(functools.partial(build_tree, leaf_size=leaf_size))(
            proj_t, codes_t, bp_t)

    parts = jax.vmap(build_one)(proj)      # leading shard axis on everything
    return A, parts, edges


def serial_reference_query(data: jax.Array, A: jax.Array, parts: dict,
                           params: LSHParams, queries: jax.Array,
                           cfg: QueryConfig, n_shards: int, leaf_size: int):
    """Runs _knn_local per shard with psum == sum across shards, serially."""
    from repro.core.query import range_query_round, exact_distances

    n, d = data.shape
    n_local = n // n_shards
    shards = data.reshape(n_shards, n_local, d)
    forests = [
        DEForest(n=n_local, leaf_size=leaf_size,
                 **{k: v[s] for k, v in parts.items()})
        for s in range(n_shards)
    ]
    K, L = params.K, params.L
    out_ids, out_d = [], []
    for q in queries:
        q_proj = project_query(q, A).reshape(L, K)
        M = min(cfg.M, forests[0].n_leaves)
        round_cap = L * M * leaf_size
        cap = min(int(params.beta * n) + cfg.k + round_cap,
                  n_local + round_cap)
        bufs = [(jnp.full((cap,), n_local, jnp.int32),
                 jnp.full((cap,), jnp.inf)) for _ in range(n_shards)]
        r = cfg.r_min
        for _ in range(cfg.max_rounds):
            counts, withins = [], []
            for s in range(n_shards):
                ids_b, d_b = bufs[s]
                new_ids, ok = range_query_round(
                    forests[s], q_proj, params.epsilon * r, cfg.M,
                    mode=cfg.mode)
                new_d = exact_distances(shards[s], q, new_ids, ok)
                new_ids = jnp.where(ok, new_ids, n_local)
                ids_b, d_b, cnt = _merge_candidates(n_local, ids_b, d_b,
                                                    new_ids, new_d)
                bufs[s] = (ids_b, d_b)
                counts.append(float(cnt))
                withins.append(float(jnp.sum(d_b <= params.c * r)))
            if sum(counts) >= params.beta * n + cfg.k or \
                    sum(withins) >= cfg.k:
                break
            r = r * params.c
        # merge per-shard top-k
        all_ids, all_d = [], []
        for s in range(n_shards):
            ids_b, d_b = bufs[s]
            kk = min(cfg.k, cap)
            negd, sel = jax.lax.top_k(-d_b, kk)
            lids = ids_b[sel]
            all_ids.append(jnp.where(lids < n_local, lids + s * n_local, n))
            all_d.append(-negd)
        cat_i = jnp.concatenate(all_ids)
        cat_d = jnp.concatenate(all_d)
        negd, sel = jax.lax.top_k(-cat_d, cfg.k)
        out_ids.append(cat_i[sel])
        out_d.append(-negd)
    return jnp.stack(out_ids), jnp.stack(out_d)


# ===========================================================================
# PDETIndex: the protocol-level sharded index (repro.api; DESIGN.md §7)
# ===========================================================================
#
# ``PDETLSH`` above partitions the *structure*: each device builds its own
# complete forest over its data shard.  That parallelizes the build (Alg. 7)
# but per-shard leaf partitions admit different candidate sets than the one
# global forest, so its equivalence to DET-LSH is statistical, not exact.
#
# ``PDETIndex`` instead partitions the *layout* of the one global forest
# (paper Alg. 8, the serving-critical phase): the code-sorted point arrays
# and leaf summaries are sharded over the mesh's data axes (a shard owns
# whole leaves), queries/A/breakpoints replicate, and each radius round is
# the fused engine's round run shard-locally, merged across shards with
# ``pmin`` — which is *exact* (min is associative and commutative in fp32,
# unlike add).  Every (tree, point) distance lives on exactly one shard and
# is computed by the identical kernel tile, so the merged per-id table —
# and therefore T1/T2, the lockstep radius schedule, and the final top-k —
# are bit-identical to ``fused_query_batch`` on one device, for ANY shard
# count.  The PDET == DET claim (paper Fig. 20/21) is thereby an exact API
# contract, not a statistical one (tests/test_pdet_api.py).


def _pdet_partition_specs(data_axes: tuple):
    """PartitionSpecs of the PDET layout, logical-name style
    (``sharding/rules.py`` conventions: 'points'/'leaves' shard over the
    placement's data axes, everything else replicates)."""
    ax = tuple(data_axes)
    return {
        "data": P(ax),                      # (n, d) rows
        "points": P(None, ax),              # (L, n_pad) sorted positions
        "points_k": P(None, ax, None),      # (L, n_pad, K|d)
        "leaves": P(None, ax),              # (L, n_leaves)
        "leaves_k": P(None, ax, None),      # (L, n_leaves, K)
        "replicated": P(),
    }


def _forest_pdet_specs(forest: DEForest, specs: dict) -> DEForest:
    return DEForest(
        point_ids=specs["points"], proj_sorted=specs["points_k"],
        codes_sorted=specs["points_k"], valid=specs["points"],
        leaf_lo=specs["leaves_k"], leaf_hi=specs["leaves_k"],
        leaf_valid=specs["leaves"], breakpoints=specs["replicated"],
        n=forest.n, leaf_size=forest.leaf_size)


def _pad_layout_to_shards(forest: DEForest, plan: FusedPlan,
                          n_shards: int) -> tuple:
    """Pad the leaf axis (and the matching point slots) so every shard
    owns the same number of whole leaves.  Padding leaves are invalid
    (never admitted) and padding point slots carry ``valid=False`` and
    the ``n`` sentinel id, so no answer can change; real sorted positions
    keep their indices (padding appends), so ``inv_perm`` is untouched."""
    n_leaves = forest.n_leaves
    pad_l = (-n_leaves) % n_shards
    if pad_l == 0:
        return forest, plan
    pad_p = pad_l * forest.leaf_size

    def pad(x, width, value):
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, width)
        return jnp.pad(x, widths, constant_values=value)

    forest = DEForest(
        n=forest.n, leaf_size=forest.leaf_size,
        point_ids=pad(forest.point_ids, pad_p, forest.n),
        proj_sorted=pad(forest.proj_sorted, pad_p, 0.0),
        codes_sorted=pad(forest.codes_sorted, pad_p, 0),
        valid=pad(forest.valid, pad_p, False),
        leaf_lo=pad(forest.leaf_lo, pad_l, 0),
        leaf_hi=pad(forest.leaf_hi, pad_l, 0),
        leaf_valid=pad(forest.leaf_valid, pad_l, False),
        breakpoints=forest.breakpoints)
    plan = FusedPlan(points_sorted=pad(plan.points_sorted, pad_p, 0.0),
                     inv_perm=plan.inv_perm)
    return forest, plan


def pdet_query_batch(forest: DEForest, A: jax.Array, params: LSHParams,
                     queries: jax.Array, cfg: QueryConfig, plan: FusedPlan,
                     mesh: Mesh, axes: tuple, *,
                     n_active=None):
    """Sharded fused c^2-k-ANN round loop (Alg. 8 over the global layout).

    Per round, each shard runs one ``range_rerank`` pass over its own
    leaves/points, folds its tree rows into id space through the (global)
    inverse permutation, and the shards merge with an exact ``pmin``; the
    replicated best-distance table then steps through the *same*
    ``fused_round_update`` as the single-device fused engine — see the
    section comment for why this makes the result bit-identical.

    Returns ``(QueryResult, shard_candidates)`` where ``shard_candidates``
    is the (n_shards,) count of (tree, point) entries scanned per shard.
    """
    if getattr(cfg, "probe_depth", 0):
        raise NotImplementedError(
            "engine 'pdet' does not support multi-probe (probe_depth > 0): "
            "each shard only sees its own leaves, so a per-shard "
            "slack ranking would admit a different probe set per device "
            "count and break the bit-identical PDET == DET contract; use "
            "engine='fused' or 'vmap' (they run on the sharded arrays), or "
            "probe_depth=0")
    n = forest.n
    B = queries.shape[0]
    K, L = params.K, params.L
    n_pad = forest.point_ids.shape[1]
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_local = n_pad // n_shards
    thresh = jnp.asarray(params.beta * n + cfg.k, jnp.float32)
    interpret = cfg.dist_impl == "pallas_interpret"
    q_proj = project_query(queries, A).reshape(B, L, K).transpose(
        1, 0, 2)                                                 # (L, B, K)
    done0 = (jnp.zeros((B,), jnp.bool_) if n_active is None
             else jnp.arange(B) >= jnp.asarray(n_active))

    from repro.kernels import ops as kops
    specs = _pdet_partition_specs(axes)

    def run(pts_local, valid_local, lo, hi, lv, bp, inv_perm, q, qp, done0):
        sidx = jnp.asarray(0, jnp.int32)
        for a in axes:          # row-major over axes — matches device_put
            sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
        off = sidx * n_local

        def cond(state):
            rnd, rounds, r, done, best, scanned = state
            return jnp.any(~done) & (rnd < cfg.max_rounds)

        def body(state):
            rnd, rounds, r, done, best, scanned = state
            r_eff = jnp.where(done, -1.0, params.epsilon * r)    # lane mask
            dmat = kops.range_rerank(
                q, qp, r_eff, lo, hi, lv, bp, pts_local, valid_local, None,
                leaf_size=forest.leaf_size, interpret=interpret,
                block_q=cfg.block_q, block_l=cfg.block_l)  # (L, B, n_local)
            # f32 accumulator: an int32 count wraps negative on large
            # (L, B, n_local) workloads (int64 needs x64); this is a work
            # counter, so f32's rounding at scale beats wrap-around.
            scanned = scanned + jnp.sum(jnp.isfinite(dmat),
                                        dtype=jnp.float32)
            # Fold this shard's tree rows into id space: a point's sorted
            # position is local iff it falls in [off, off + n_local).
            rel = inv_perm - off                                 # (L, n)
            here = (rel >= 0) & (rel < n_local)
            safe = jnp.clip(rel, 0, n_local - 1)
            g = jnp.take_along_axis(dmat, safe[:, None, :], axis=2)
            g = jnp.where(here[:, None, :], g, jnp.inf)
            by_id = jnp.min(g, axis=0)                           # (B, n)
            by_id = jax.lax.pmin(by_id, axes)    # exact cross-shard merge
            best, r, done, rounds = fused_round_update(
                best, by_id, r, done, rounds, rnd, params=params, k=cfg.k,
                thresh=thresh)
            return rnd + 1, rounds, r, done, best, scanned

        state0 = (jnp.asarray(0, jnp.int32), jnp.zeros((B,), jnp.int32),
                  jnp.full((B,), cfg.r_min, jnp.float32), done0,
                  jnp.full((B, n), jnp.inf, jnp.float32),
                  jnp.asarray(0.0, jnp.float32))
        rnd, rounds, r, done, best, scanned = jax.lax.while_loop(
            cond, body, state0)
        ids, dists, count = fused_topk(best, cfg.k, n)
        return ids, dists, rounds, count, r, scanned[None]

    in_specs = (specs["points_k"], specs["points"], specs["leaves_k"],
                specs["leaves_k"], specs["leaves"], P(), P(), P(), P(), P())
    out_specs = (P(), P(), P(), P(), P(), P(axes))
    ids, dists, rounds, count, r, scanned = shard_map(
        run, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(
            plan.points_sorted, forest.valid, forest.leaf_lo,
            forest.leaf_hi, forest.leaf_valid, forest.breakpoints,
            plan.inv_perm, queries, q_proj, done0)
    res = QueryResult(ids=ids, dists=dists, rounds=rounds,
                      n_candidates=count, final_r=r)
    return res, scanned


@dataclasses.dataclass
class PDETIndex:
    """The sharded PDET-LSH index behind the ``repro.api`` surface.

    Satisfies the ``AnnIndex`` protocol end-to-end: built from an
    ``IndexSpec`` whose ``placement`` names the mesh, searched through
    ``SearchRequest``/``SearchResult`` via the ``pdet`` engine (with
    per-shard counters in ``SearchStats``), snapshotted as per-shard files
    (``repro.api.load`` reshards onto whatever device count is present),
    and served by ``LSHService`` purely through the protocols.
    """

    params: LSHParams
    A: jax.Array               # replicated
    forest: DEForest           # the ONE global forest, layout-sharded
    data: jax.Array            # (n, d), rows sharded over the data axes
    plan: FusedPlan            # points_sorted sharded, inv_perm replicated
    mesh: Mesh
    placement: "object"        # repro.api.PlacementSpec
    spec: Optional["object"] = dataclasses.field(
        default=None, repr=False, compare=False)
    _r_min_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    # ------------------------------------------------------------------
    # Build
    # ------------------------------------------------------------------

    @classmethod
    def from_spec(cls, data: jax.Array, key: jax.Array, spec, *,
                  mesh: Optional[Mesh] = None) -> "PDETIndex":
        """Build from an ``IndexSpec`` with a ``placement``.

        The forest is built by the *identical* code path as
        ``DETLSH.from_spec`` on the same spec minus placement (same key,
        same arrays — the foundation of the bit-identity contract), then
        the layout is sharded onto the placement's mesh.
        """
        placement = spec.placement
        if placement is None:
            raise ValueError("PDETIndex.from_spec needs spec.placement "
                             "(use repro.api.build for unplaced specs)")
        from repro.core import DETLSH
        base_spec = dataclasses.replace(spec, placement=None)
        det = DETLSH.from_spec(data, key, base_spec)
        return cls.from_detlsh(det, placement, mesh=mesh, spec=spec)

    @classmethod
    def from_detlsh(cls, det, placement, *, mesh: Optional[Mesh] = None,
                    spec=None) -> "PDETIndex":
        """Shard an already-built single-device index onto a mesh.

        When the leaf count is not a multiple of the shard count, the
        layout is padded with *invalid* leaves (and their empty point
        slots) up to one: invalid leaves are never admitted and padding
        point slots carry ``valid=False``, so the padding changes no
        answer — bit-identity survives any shard count.  Data rows shard
        when divisible, else replicate (they only feed the fallback
        engines, host-side estimates, and snapshots).
        """
        if mesh is None:
            from repro.launch.mesh import mesh_from_placement
            mesh = mesh_from_placement(placement)
        axes = placement.data_axes
        n_shards = placement.n_shards
        forest, plan = _pad_layout_to_shards(det.forest, det.fused_plan(),
                                             n_shards)
        specs = _pdet_partition_specs(axes)

        def put(x, spec_):
            return jax.device_put(x, NamedSharding(mesh, spec_))

        data_spec = (specs["data"] if det.data.shape[0] % n_shards == 0
                     else specs["replicated"])
        fspecs = _forest_pdet_specs(forest, specs)
        sharded_forest = DEForest(
            n=forest.n, leaf_size=forest.leaf_size,
            **{k: put(getattr(forest, k), getattr(fspecs, k))
               for k in ("point_ids", "proj_sorted", "codes_sorted",
                         "valid", "leaf_lo", "leaf_hi", "leaf_valid",
                         "breakpoints")})
        idx = cls(
            params=det.params,
            A=put(det.A, specs["replicated"]),
            forest=sharded_forest,
            data=put(det.data, data_spec),
            plan=FusedPlan(
                points_sorted=put(plan.points_sorted, specs["points_k"]),
                inv_perm=put(plan.inv_perm, specs["replicated"])),
            mesh=mesh, placement=placement,
            spec=spec if spec is not None else det.spec)
        idx._r_min_cache.update(det._r_min_cache)
        return idx

    # ------------------------------------------------------------------
    # AnnIndex protocol
    # ------------------------------------------------------------------

    @property
    def n_points(self) -> int:
        return int(self.data.shape[0])

    @property
    def n_shards(self) -> int:
        return self.placement.n_shards

    def r_min_for(self, k: int, queries: jax.Array | None = None) -> float:
        """Cached per-(index, k) starting radius — the same estimator over
        the same rows as ``DETLSH.r_min_for``, so a PDET and its
        single-device twin start every search at the same radius."""
        if k not in self._r_min_cache:
            from repro.core import estimate_r_min
            probes = (queries if queries is not None
                      else self.data[: min(64, self.data.shape[0])])
            self._r_min_cache[k] = estimate_r_min(self.data, probes, k,
                                                  self.params.c)
        return self._r_min_cache[k]

    def search(self, queries: jax.Array, request=None):
        """Typed batched search (``repro.api``).  Resolves through the
        registry with this index's mesh declared active, so ``'auto'``
        routes to the ``pdet`` engine; mode/explicit-engine fallbacks
        (e.g. 'strict' -> vmap) run on the sharded arrays directly."""
        from repro.api import registry
        from repro.api.request import SearchRequest, SearchResult, \
            SearchStats
        req = request or SearchRequest()
        r_min, cached = req.r_min, False
        if r_min is None:
            cached = req.k in self._r_min_cache
            probes = queries[: req.n_active] if req.n_active else queries
            r_min = self.r_min_for(req.k, probes)
        spec = self.spec
        default_engine = spec.engine if spec is not None else "auto"
        cfg = req.to_query_config(
            default_engine=default_engine, r_min=r_min,
            block_q=spec.block_q if spec is not None else 8,
            block_l=spec.block_l if spec is not None else 8,
            default_probe_depth=spec.probe_depth if spec is not None else 0)
        engine = registry.resolve_engine(
            cfg.engine, mode=cfg.mode, batch=queries.shape[0],
            mesh_devices=self.placement.n_devices)
        if engine == "pdet" and cfg.probe_depth > 0 and \
                (req.engine or default_engine) != "pdet":
            # Multi-probe is not expressible per-shard (see
            # pdet_query_batch); 'auto' falls back to the fused engine on
            # the sharded arrays.  An *explicit* engine='pdet' with
            # probe_depth > 0 falls through and raises there.
            engine = "fused"
        shard_cands = psum_rounds = merge_size = None
        if engine == "pdet":
            res, shard_cands = pdet_query_batch(
                self.forest, self.A, self.params, queries, cfg, self.plan,
                self.mesh, self.placement.data_axes, n_active=req.n_active)
            psum_rounds = jnp.max(res.rounds)
            merge_size = queries.shape[0] * self.forest.n
        else:
            # Mode / explicit-engine fallback: the single-device engines
            # run on the sharded arrays (XLA inserts the collectives).
            cfg = dataclasses.replace(cfg, engine=engine)
            plan = self.plan if engine == "fused" else None
            res = knn_query_batch(self.data, self.forest, self.A,
                                  self.params, queries, cfg, plan=plan,
                                  n_active=req.n_active)
        return SearchResult(
            ids=res.ids, dists=res.dists,
            stats=SearchStats(engine=engine, r_min=float(r_min),
                              r_min_cached=cached, rounds=res.rounds,
                              n_candidates=res.n_candidates,
                              final_r=res.final_r,
                              shard_candidates=shard_cands,
                              psum_rounds=psum_rounds,
                              merge_size=merge_size,
                              probed_leaves=res.probed_leaves,
                              probe_candidates=res.probe_candidates),
            raw=res)

    def save(self, path) -> None:
        """Write a sharded snapshot directory: per-shard npz + shard map
        in MANIFEST.json (``repro.api.load`` reshards on load)."""
        from repro.api import persist
        persist.save_pdet(self, path)

    def index_size_bytes(self) -> int:
        return self.forest.size_bytes() + self.A.size * 4


def _layout_mesh_axes(arr):
    """Recover (mesh, data_axes) from a PDET-sharded array's placement —
    the engine-registry entry point has only the uniform engine signature,
    so the mesh travels with the arrays themselves."""
    sharding = getattr(arr, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is None or spec is None or len(spec) < 2 or spec[1] is None:
        raise ValueError(
            "engine 'pdet' needs a mesh-sharded index layout (build via "
            "repro.api.build with an IndexSpec placement); the fused-plan "
            "arrays of this index are not sharded")
    axes = spec[1]
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    return mesh, axes


def _run_pdet_engine(data, forest, A, params, queries, cfg, *,
                     plan=None, live=None, live_sorted=None,
                     n_active=None) -> QueryResult:
    """Registry entry point for engine='pdet'."""
    del data
    if live is not None or live_sorted is not None:
        raise NotImplementedError(
            "engine 'pdet' serves the static sharded index; tombstones "
            "(live masks) belong to the streaming index's engines")
    if plan is None:
        raise ValueError("engine 'pdet' needs the index's sharded "
                         "FusedPlan (plan=)")
    mesh, axes = _layout_mesh_axes(plan.points_sorted)
    res, _ = pdet_query_batch(forest, A, params, queries, cfg, plan,
                              mesh, axes, n_active=n_active)
    return res


engine_registry.register_engine(
    "pdet", _run_pdet_engine, modes=("leaf",), min_batch=1, priority=20,
    needs_mesh=True,
    doc="shard_map'd fused round over the mesh-sharded global layout "
        "(Alg. 8); exact pmin merge => bit-identical to 'fused' on one "
        "device for any shard count")
