"""DET-LSH query phase (paper §III-C: Alg. 3, 4, 5).

The c^2-k-ANN query issues (r,c)-ANN rounds with radii r, c*r, c^2*r, ...
Each round performs a range query with projected radius eps*r in all L
DE-Trees, accumulates unique candidates into S, computes their *exact*
original-space distances, and terminates when

    (T1)  |S| >= beta*n + k                                   (Alg. 5 line 7)
    (T2)  at least k candidates satisfy ||o, q|| <= c * r     (Alg. 5 line 9)

returning the top-k of S by exact distance.  Both conditions — and the use of
*unique* candidate counts — match the paper exactly, so Theorems 1-3 apply.

TPU adaptation of the range query (Alg. 3 + the §VI-B2 optimizations):
  * leaf LB distances are computed vectorized over all leaf summaries;
  * the paper's "priority queue of leaves ordered by LB" becomes
    ``lax.top_k(-LB, M)``;
  * the paper's optimization #1 ("add all points of a leaf whenever its LB
    does not exceed r") is the default admission rule (``mode='leaf'``);
    ``mode='strict'`` reproduces the unoptimized Alg. 3 (filter by exact
    projected distance), used by the Fig. 8 benchmark.

The round structure checks termination after each round of L trees rather
than after every tree; this can only make S larger at return time, which
preserves the guarantee (see docs/DESIGN.md §2).

Two query engines (docs/DESIGN.md §3):

  * ``engine='fused'`` (default for batches in ``mode='leaf'``) — the whole
    batch advances through radius rounds together.  Each round is ONE fused
    ``range_rerank`` kernel pass (leaf LB + radius admission + candidate
    gather + exact rerank, tiled query-block x leaf-block over all L trees),
    and the candidate set is maintained as a per-query dense
    best-exact-distance table, so merging a round costs one gather + min —
    no per-round sort.  Done lanes carry a -1 radius and admit nothing
    (active-lane masking).  Admission is leaf-granular without the top-M
    cut: a superset of the vmap engine's candidates, so Theorems 1-3 still
    apply.
  * ``engine='vmap'`` — the seed per-query ``while_loop``, vmapped.  Kept
    for ``mode='strict'``, single queries, and as the benchmark baseline.
    Its per-round candidate merge is the incremental bitmap+cursor scheme
    of ``core.candidates`` (the seed's O(cap log cap) argsort-per-round,
    ``_merge_candidates``, is retained below as the semantics-of-record
    oracle for the property tests).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.api import registry as engine_registry
from repro.core import candidates as cand
from repro.core.detree import DEForest, leaf_bounds
from repro.core.hashing import project_query
from repro.core.theory import LSHParams


class QueryResult(NamedTuple):
    ids: jax.Array        # (k,) int32 — candidate point indices (n = invalid)
    dists: jax.Array      # (k,) f32   — exact original-space distances
    rounds: jax.Array     # ()  int32  — number of radius enlargements + 1
    n_candidates: jax.Array  # () int32 — |S| (unique) at termination
    final_r: jax.Array    # ()  f32
    # Multi-probe counters (appended, defaulted: paths that never probe —
    # rc_ann, the legacy distributed query — leave them None).
    probed_leaves: Optional[jax.Array] = None    # () int32 — near-miss leaves
    probe_candidates: Optional[jax.Array] = None  # () int32 — their candidates


# ---------------------------------------------------------------------------
# Range query over the forest (one round, all L trees)
# ---------------------------------------------------------------------------

def range_query_round(forest: DEForest, q_proj: jax.Array, r_proj: jax.Array,
                      M: int, *, mode: str = "leaf",
                      bounds_impl: str = "auto",
                      live: Optional[jax.Array] = None,
                      probe_depth: int = 0, with_stats: bool = False):
    """Range query with projected radius ``r_proj`` in all L trees.

    q_proj: (L, K) projected query.  ``live`` is an optional (n,) bool
    tombstone mask in point-id order (None = all live); dead points are
    rejected at admission, before the exact rerank.

    ``probe_depth > 0`` additionally admits, per tree, the probe_depth
    near-miss leaves — the smallest-LB valid leaves with LB *above* the
    radius, within the same top-M LB cut the engine already takes (the
    multi-probe sequence; docs/DESIGN.md §11).  With probe_depth=0 the
    admitted set is exactly the pre-probe rule.

    Returns (ids, ok): ids (L*M*leaf_size,) int32 candidate point ids, ok
    bool mask.  With ``with_stats=True`` also returns scalar int32 counters
    (probed_leaves, probe_candidates) summed over trees.
    """
    leaf_size = forest.leaf_size
    M = min(M, forest.n_leaves)

    def per_tree(pids, proj_s, lo, hi, lvalid, bp, qp):
        lb, _ = leaf_bounds(qp, lo, hi, lvalid, bp, impl=bounds_impl)
        neg, leaf_idx = jax.lax.top_k(-lb, M)                 # best-M by LB
        lb_m = -neg                                           # ascending LB
        leaf_ok = lb_m <= r_proj                              # LB <= eps*r
        if probe_depth > 0:
            outside = (~leaf_ok) & jnp.isfinite(lb_m)
            rank = jnp.cumsum(outside.astype(jnp.int32))      # slack order
            probe_ok = outside & (rank <= probe_depth)
            admit = leaf_ok | probe_ok
        else:
            probe_ok = jnp.zeros_like(leaf_ok)
            admit = leaf_ok
        gidx = leaf_idx[:, None] * leaf_size + jnp.arange(leaf_size)[None, :]
        gidx = gidx.reshape(-1)                               # (M*leaf_size,)
        ids = pids[gidx]
        ok = jnp.repeat(admit, leaf_size) & (ids < forest.n)
        if live is not None:
            ok = ok & live[jnp.clip(ids, 0, forest.n - 1)]
        if mode == "strict":
            pts = proj_s[gidx]                                # (M*ls, K)
            d = jnp.sqrt(jnp.sum((pts - qp[None, :]) ** 2, axis=1))
            ok = ok & (d <= r_proj)
        probed = probe_ok.sum().astype(jnp.int32)
        pcand = (ok & jnp.repeat(probe_ok, leaf_size)).sum().astype(jnp.int32)
        return ids, ok, probed, pcand

    ids, ok, probed, pcand = jax.vmap(per_tree)(
        forest.point_ids, forest.proj_sorted, forest.leaf_lo, forest.leaf_hi,
        forest.leaf_valid, forest.breakpoints, q_proj)
    if with_stats:
        return ids.reshape(-1), ok.reshape(-1), probed.sum(), pcand.sum()
    return ids.reshape(-1), ok.reshape(-1)


# ---------------------------------------------------------------------------
# Candidate set maintenance (unique ids, exact distances)
# ---------------------------------------------------------------------------

def _merge_candidates(n: int, buf_ids: jax.Array, buf_d: jax.Array,
                      new_ids: jax.Array, new_d: jax.Array) -> tuple[
                          jax.Array, jax.Array, jax.Array]:
    """Seed sort-based merge — kept as the semantics-of-record oracle.

    The query engines now use ``core.candidates.merge_round`` (per-round cost
    scales with the round size, not the buffer; see that module).  This
    function re-sorts the whole buffer every call and remains only as the
    reference the incremental scheme is property-tested against, and for the
    distributed (multi-shard) path.

    Merges new candidates into the fixed-size buffer, dedup by id.  Buffer
    keeps the ``cap`` smallest-distance unique candidates; returns
    (ids, dists, unique_count_in_buffer).  Invalid slots carry id = n and
    dist = +inf.  Because the loop terminates as soon as the unique count
    reaches beta*n + k and cap >= beta*n + k + round_cap, no unique candidate
    is ever dropped before termination triggers.
    """
    cap = buf_ids.shape[0]
    ids = jnp.concatenate([buf_ids, new_ids])
    d = jnp.concatenate([buf_d, new_d])
    order = jnp.argsort(ids, stable=True)                     # sentinels last
    ids_s = ids[order]
    d_s = d[order]
    first = jnp.concatenate([jnp.array([True]), ids_s[1:] != ids_s[:-1]])
    is_real = ids_s < n
    keep = first & is_real
    d_s = jnp.where(keep, d_s, jnp.inf)
    ids_s = jnp.where(keep, ids_s, n)
    # Retain the cap best by distance.
    negd, sel = jax.lax.top_k(-d_s, cap)
    out_ids = ids_s[sel]
    out_d = -negd
    count = jnp.sum(out_ids < n).astype(jnp.int32)
    return out_ids, out_d, count


def exact_distances(data: jax.Array, q: jax.Array, ids: jax.Array,
                    ok: jax.Array, *, impl: str = "auto") -> jax.Array:
    """Exact original-space distances for candidate ids ((paper's rerank)."""
    n = data.shape[0]
    safe = jnp.clip(ids, 0, n - 1)
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        pts = jnp.take(data, safe, axis=0)
        d = kops.l2_rerank(q[None, :], pts,
                           interpret=(impl == "pallas_interpret"))[0]
    else:
        pts = jnp.take(data, safe, axis=0)
        d = jnp.sqrt(jnp.maximum(jnp.sum((pts - q[None, :]) ** 2, axis=1), 0.0))
    return jnp.where(ok, d, jnp.inf)


# ---------------------------------------------------------------------------
# c^2-k-ANN query (Alg. 5)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueryConfig:
    k: int = 50
    M: int = 8                 # leaves fetched per tree per round (vmap engine)
    cap: int = 0               # candidate buffer (0 = auto: beta*n + k + round)
    r_min: float = 1.0
    max_rounds: int = 48
    mode: str = "leaf"         # 'leaf' (optimized, default) | 'strict'
    dist_impl: str = "auto"
    bounds_impl: str = "auto"
    engine: str = "auto"       # batch engine: 'auto' or a registered name
    block_q: int = 8           # fused kernel query-tile
    block_l: int = 8           # fused kernel leaf-tile
    probe_depth: int = 0       # near-miss leaves admitted per (tree, round)

    def __post_init__(self):
        # Eager validation: a typo'd engine/mode/impl or a non-positive
        # count must fail here with the valid choices, not silently
        # misbehave deep in the radius-round loop.
        from repro.api.request import IMPLS, MODES, _check_choice, \
            _check_positive
        _check_positive("k", self.k)
        _check_positive("M", self.M)
        _check_positive("max_rounds", self.max_rounds)
        _check_positive("cap", self.cap, minimum=0)
        _check_positive("block_q", self.block_q)
        _check_positive("block_l", self.block_l)
        _check_positive("probe_depth", self.probe_depth, minimum=0)
        if not self.r_min > 0.0:
            raise ValueError(f"r_min must be positive, got {self.r_min!r}")
        _check_choice("mode", self.mode, MODES)
        _check_choice("dist_impl", self.dist_impl, IMPLS)
        _check_choice("bounds_impl", self.bounds_impl, IMPLS)
        engine_registry.validate_engine_name(self.engine)
        if self.probe_depth and self.mode == "strict":
            raise ValueError(
                "mode='strict' reproduces the unoptimized Alg. 3 per-point "
                "filter and admits no near-miss leaves; probe_depth must be "
                f"0 in strict mode (got {self.probe_depth})")


def _auto_cap(n: int, params: LSHParams, cfg: QueryConfig,
              forest: DEForest) -> int:
    round_cap = params.L * min(cfg.M, forest.n_leaves) * forest.leaf_size
    need = int(params.beta * n) + cfg.k
    return max(cfg.cap, need + round_cap) if cfg.cap else need + round_cap


def knn_query(data: jax.Array, forest: DEForest, A: jax.Array,
              params: LSHParams, q: jax.Array,
              cfg: QueryConfig, *, live: Optional[jax.Array] = None,
              active: jax.Array | bool = True,
              r_min: Optional[jax.Array | float] = None) -> QueryResult:
    """Answer one c^2-k-ANN query (Alg. 5).  q: (d,).

    ``live`` is an optional (n,) bool tombstone mask (streaming index
    deletes); ``active=False`` marks the lane done from round 0 (used for
    pad lanes in partial batches — the radius loop never runs for them).
    ``r_min`` (float or traced scalar) overrides ``cfg.r_min``.
    """
    n = data.shape[0]
    K, L = params.K, params.L
    cap = _auto_cap(n, params, cfg, forest)
    q_proj = project_query(q, A).reshape(L, K)                  # Alg. 5 line 4
    thresh = jnp.asarray(params.beta * n + cfg.k, jnp.float32)

    def cond(state):
        rnd, r, cs, done, probed, pcand = state
        return (~done) & (rnd < cfg.max_rounds)

    def body(state):
        rnd, r, cs, done, probed, pcand = state
        new_ids, ok, pl, pc = range_query_round(
            forest, q_proj, params.epsilon * r, cfg.M, mode=cfg.mode,
            bounds_impl=cfg.bounds_impl, live=live,
            probe_depth=cfg.probe_depth, with_stats=True)       # line 5
        new_d = exact_distances(data, q, new_ids, ok, impl=cfg.dist_impl)
        new_ids = jnp.where(ok, new_ids, n)
        cs = cand.merge_round(n, cs, new_ids, new_d)
        t1 = cs.count.astype(jnp.float32) >= thresh             # line 7
        within = jnp.sum(cs.dists <= params.c * r).astype(jnp.int32)
        t2 = within >= cfg.k                                    # line 9
        done = t1 | t2
        r = jnp.where(done, r, r * params.c)                    # line 11
        return rnd + 1, r, cs, done, probed + pl, pcand + pc

    r0 = cfg.r_min if r_min is None else r_min
    state0 = (jnp.asarray(0, jnp.int32), jnp.asarray(r0, jnp.float32),
              cand.init_state(n, cap), ~jnp.asarray(active),
              jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
    rnd, r, cs, done, probed, pcand = jax.lax.while_loop(cond, body, state0)

    negd, sel = jax.lax.top_k(-cs.dists, cfg.k)                 # final rerank
    return QueryResult(ids=cs.ids[sel], dists=-negd, rounds=rnd,
                       n_candidates=cs.count, final_r=r,
                       probed_leaves=probed, probe_candidates=pcand)


# ---------------------------------------------------------------------------
# Fused batched engine (docs/DESIGN.md §3)
# ---------------------------------------------------------------------------

class FusedPlan(NamedTuple):
    """Per-index constants of the fused engine, computed once per forest.

    points_sorted: (L, n_pad, d) original-space points in each tree's
        code-sorted order — turns the candidate gather into contiguous
        streaming (a leaf is a contiguous block).
    inv_perm: (L, n) int32 — position of point i in tree l's sorted order;
        lets a round's per-tree distance rows fold into the id-indexed
        candidate table with a gather instead of a scatter.
    """
    points_sorted: jax.Array
    inv_perm: jax.Array


def make_fused_plan(data: jax.Array, forest: DEForest) -> FusedPlan:
    n = forest.n
    safe = jnp.clip(forest.point_ids, 0, n - 1)                  # (L, n_pad)
    pts = jnp.take(data, safe, axis=0)                           # (L, n_pad, d)
    pts = pts * forest.valid[..., None].astype(pts.dtype)
    positions = jnp.arange(forest.point_ids.shape[1], dtype=jnp.int32)

    def inv_one(ids_l, valid_l):
        tgt = jnp.where(valid_l, ids_l, n)
        return jnp.zeros((n,), jnp.int32).at[tgt].set(positions, mode="drop")

    inv = jax.vmap(inv_one)(forest.point_ids, forest.valid)      # (L, n)
    return FusedPlan(points_sorted=pts, inv_perm=inv)


def fused_round_update(best: jax.Array, by_id: jax.Array, r: jax.Array,
                       done: jax.Array, rounds: jax.Array, rnd: jax.Array,
                       *, params: LSHParams, k: int, thresh: jax.Array):
    """Fold one round's per-id distance table into the loop state.

    The single source of truth for the fused-style T1/T2 bookkeeping: both
    ``fused_query_batch`` and the sharded ``pdet`` engine
    (core/distributed.py) run exactly this update, which is what makes the
    PDET == DET bit-identity contract hold by construction — the sharded
    round merges shards with ``pmin`` (min is exact), then steps through
    the identical state transition.
    """
    best = jnp.minimum(best, by_id)
    count = jnp.sum(best < jnp.inf, axis=1).astype(jnp.int32)
    t1 = count.astype(jnp.float32) >= thresh                 # line 7
    within = jnp.sum(best <= params.c * r[:, None], axis=1)
    t2 = within >= k                                         # line 9
    rounds = jnp.where(done, rounds, rnd + 1)                # per lane
    done = done | t1 | t2
    r = jnp.where(done, r, r * params.c)                     # line 11
    return best, r, done, rounds


def fused_topk(best: jax.Array, k: int, n: int) -> tuple[
        jax.Array, jax.Array, jax.Array]:
    """Final (ids, dists, unique-count) over the dense best-distance table
    (shared by the fused and pdet engines)."""
    negd, sel = jax.lax.top_k(-best, k)
    dists = -negd
    ids = jnp.where(jnp.isfinite(dists), sel.astype(jnp.int32), n)
    count = jnp.sum(best < jnp.inf, axis=1).astype(jnp.int32)
    return ids, dists, count


def fused_query_batch(data: jax.Array, forest: DEForest, A: jax.Array,
                      params: LSHParams, queries: jax.Array,
                      cfg: QueryConfig,
                      plan: Optional[FusedPlan] = None, *,
                      live_sorted: Optional[jax.Array] = None,
                      n_active: Optional[jax.Array | int] = None,
                      r_min: Optional[jax.Array | float] = None
                      ) -> QueryResult:
    """Batched c^2-k-ANN: all lanes advance through radius rounds together.

    Per round: ONE fused range_rerank pass over (L trees x query blocks x
    leaf blocks) returns exact distances for every point whose leaf is
    admitted at each lane's current radius (-1 for done lanes => no work),
    then the round folds into a per-query dense best-distance table with a
    gather + elementwise min.  |S| is the table's finite count — the same
    unique-candidate count Alg. 5 tracks, so T1/T2 and Theorems 1-3 are
    unchanged (the admitted set is a superset of the vmap engine's;
    docs/DESIGN.md §3).

    ``live_sorted`` is an optional (L, n_pad) bool tombstone mask in each
    tree's code-sorted order (the streaming index's delete path): dead
    points emit +inf inside the kernel and never become candidates.
    ``n_active`` (int or scalar array) marks lanes >= n_active done from
    round 0 with r_eff = -1 — pad lanes of a partial batch admit nothing
    and skip all MXU work (see serving/lsh_service.py).  ``r_min`` (float
    or traced scalar) overrides ``cfg.r_min``.

    With ``cfg.probe_depth > 0`` the leaf-LB table (radius-independent) is
    computed once up front and every round widens each lane's radius
    *per tree* to also admit the probe_depth nearest near-miss leaves
    (docs/DESIGN.md §11).  Unlike the vmap engine there is no top-M cut, so
    the probe set ranges over all leaves of the tree.  probe_depth=0 takes
    the exact pre-probe path (1-D radii, no LB pre-pass) — bit-identical.
    """
    n = data.shape[0]
    B = queries.shape[0]
    K, L = params.K, params.L
    if plan is None:
        plan = make_fused_plan(data, forest)
    q_proj = project_query(queries, A).reshape(B, L, K).transpose(
        1, 0, 2)                                                 # (L, B, K)
    thresh = jnp.asarray(params.beta * n + cfg.k, jnp.float32)
    interpret = cfg.dist_impl == "pallas_interpret"
    nl, ls = forest.n_leaves, forest.leaf_size

    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    if cfg.probe_depth > 0:
        # Leaf LBs depend only on (query, leaf), not the radius: one
        # (L, B, nl) pre-pass ranks probe candidates for every round.
        probe_lb = kref.forest_leaf_lb(
            q_proj, forest.leaf_lo.astype(jnp.int32),
            forest.leaf_hi.astype(jnp.int32), forest.leaf_valid,
            forest.breakpoints)

    def cond(state):
        rnd, rounds, r, done, best, probed, pcand = state
        return jnp.any(~done) & (rnd < cfg.max_rounds)

    def body(state):
        rnd, rounds, r, done, best, probed, pcand = state
        r_eff = jnp.where(done, -1.0, params.epsilon * r)        # lane mask
        if cfg.probe_depth > 0:
            r_adm, probe_mask = kref.probe_radii_from_lb(
                probe_lb, r_eff, cfg.probe_depth)                # (L, B)
        else:
            r_adm = r_eff                                        # (B,) shared
        dmat = kops.range_rerank(
            queries, q_proj, r_adm, forest.leaf_lo, forest.leaf_hi,
            forest.leaf_valid, forest.breakpoints, plan.points_sorted,
            forest.valid, live_sorted,
            leaf_size=forest.leaf_size, interpret=interpret,
            block_q=cfg.block_q, block_l=cfg.block_l)            # (L, B, n_pad)
        if cfg.probe_depth > 0:
            probed = probed + probe_mask.sum((0, 2)).astype(jnp.int32)
            per_leaf = jnp.isfinite(dmat.reshape(L, B, nl, ls)).sum(-1)
            pcand = pcand + jnp.where(probe_mask, per_leaf,
                                      0).sum((0, 2)).astype(jnp.int32)
        # Fold the round into the id-indexed table: inv_perm turns each
        # tree's sorted-order row into id order (gather, not scatter).
        # One (B, n) gather per tree: a batched take_along_axis would
        # materialize (L, B, n) index and transpose buffers (GBs at n=1M).
        with jax.named_scope("fold"):
            by_id = functools.reduce(jnp.minimum, [
                jnp.take(dmat[l], plan.inv_perm[l], axis=1)
                for l in range(L)])                              # (B, n)
        best, r, done, rounds = fused_round_update(
            best, by_id, r, done, rounds, rnd, params=params, k=cfg.k,
            thresh=thresh)
        return rnd + 1, rounds, r, done, best, probed, pcand

    done0 = (jnp.zeros((B,), jnp.bool_) if n_active is None
             else jnp.arange(B) >= jnp.asarray(n_active))
    state0 = (jnp.asarray(0, jnp.int32),
              jnp.zeros((B,), jnp.int32),
              jnp.full((B,), cfg.r_min if r_min is None else r_min,
                       jnp.float32),
              done0,
              jnp.full((B, n), jnp.inf, jnp.float32),
              jnp.zeros((B,), jnp.int32),
              jnp.zeros((B,), jnp.int32))
    rnd, rounds, r, done, best, probed, pcand = jax.lax.while_loop(
        cond, body, state0)

    ids, dists, count = fused_topk(best, cfg.k, n)
    return QueryResult(ids=ids, dists=dists, rounds=rounds,
                       n_candidates=count, final_r=r,
                       probed_leaves=probed, probe_candidates=pcand)


# Below this batch size the fused engine's full-forest streaming pass is not
# amortized and the per-query vmap path wins (measured in BENCH_query.json).
_FUSED_MIN_BATCH = 8


def live_in_sorted_order(forest: DEForest,
                         live: jax.Array) -> jax.Array:
    """Translate an (n,) id-order tombstone mask to each tree's code-sorted
    order: (L, n_pad) bool, padding rows dead.  This is the layout the fused
    kernel's per-tile live mask consumes."""
    safe = jnp.clip(forest.point_ids, 0, forest.n - 1)
    return live[safe] & forest.valid


def _program_operands(queries, cfg: QueryConfig, engine: str, n_active):
    """Split a search into its static key and traced scalars.

    The starting radius and the active-lane count change from call to call
    (a per-request ``r_min``, a serve bucket's fill level), so they travel
    as traced f32 / int32 scalars and ``cfg`` keeps one fixed ``r_min``
    (and the resolved engine name): every such call reuses the program
    compiled for its shapes.  ``n_active=None`` means every lane is live.
    """
    r_min = jnp.asarray(cfg.r_min, jnp.float32)
    n_act = jnp.asarray(queries.shape[0] if n_active is None else n_active,
                        jnp.int32)
    return dataclasses.replace(cfg, r_min=1.0, engine=engine), r_min, n_act


@functools.partial(jax.jit, static_argnames=("params", "cfg"))
def _vmap_program(data, forest, A, queries, live, r_min, n_active, *,
                  params: LSHParams, cfg: QueryConfig) -> QueryResult:
    active = jnp.arange(queries.shape[0]) < n_active
    fn = functools.partial(knn_query, data, forest, A, params, cfg=cfg,
                           live=live, r_min=r_min)
    return jax.vmap(lambda q, a: fn(q, active=a))(queries, active)


@functools.partial(jax.jit, static_argnames=("params", "cfg"))
def _fused_program(data, forest, A, queries, plan, live, live_sorted, r_min,
                   n_active, *, params: LSHParams,
                   cfg: QueryConfig) -> QueryResult:
    if live_sorted is None and live is not None:
        live_sorted = live_in_sorted_order(forest, live)
    return fused_query_batch(data, forest, A, params, queries, cfg,
                             plan=plan, live_sorted=live_sorted,
                             n_active=n_active, r_min=r_min)


def _run_vmap_engine(data, forest, A, params, queries, cfg, *,
                     plan=None, live=None, live_sorted=None,
                     n_active=None) -> QueryResult:
    """Registry entry point for engine='vmap' (ignores plan/live_sorted):
    one compiled program per (shapes, static config)."""
    del plan, live_sorted
    cfg, r_min, n_act = _program_operands(queries, cfg, "vmap", n_active)
    return _vmap_program(data, forest, A, queries, live, r_min, n_act,
                         params=params, cfg=cfg)


def _run_fused_engine(data, forest, A, params, queries, cfg, *,
                      plan=None, live=None, live_sorted=None,
                      n_active=None) -> QueryResult:
    """Registry entry point for engine='fused' (derives live_sorted): one
    compiled program per (shapes, static config)."""
    cfg, r_min, n_act = _program_operands(queries, cfg, "fused", n_active)
    return _fused_program(data, forest, A, queries, plan, live, live_sorted,
                          r_min, n_act, params=params, cfg=cfg)


engine_registry.register_engine(
    "vmap", _run_vmap_engine, modes=("leaf", "strict"), min_batch=1,
    priority=0,
    doc="per-query while_loop, vmapped; the only engine reproducing the "
        "unoptimized strict Alg. 3 per-point filter")
engine_registry.register_engine(
    "fused", _run_fused_engine, modes=("leaf",),
    min_batch=_FUSED_MIN_BATCH, priority=10,
    doc="one-pass Pallas range_rerank over all L trees; leaf-granular "
        "admission (a superset of vmap's — Theorems 1-3 unchanged)")


def knn_query_batch(data: jax.Array, forest: DEForest, A: jax.Array,
                    params: LSHParams, queries: jax.Array,
                    cfg: QueryConfig,
                    plan: Optional[FusedPlan] = None, *,
                    live: Optional[jax.Array] = None,
                    live_sorted: Optional[jax.Array] = None,
                    n_active: Optional[jax.Array | int] = None
                    ) -> QueryResult:
    """Batched c^2-k-ANN over a (b, d) query batch.

    Dispatches through the ``repro.api.registry`` engine registry (fused
    by default at batch >= 8, vmap otherwise / for 'strict') according to
    ``cfg.engine`` / ``cfg.mode`` and the (static) batch size.  The
    engine is resolved here, on the host; each built-in engine then runs
    one jitted program per (shapes, static config), reused by every later
    call whatever its ``r_min`` and ``n_active`` (docs/DESIGN.md §3).

    ``live`` ((n,) bool, id order) / ``live_sorted`` ((L, n_pad) bool,
    code-sorted order) carry the streaming index's tombstones — pass either
    (the other is derived); None means every point is live.  ``n_active``
    marks trailing pad lanes of a partial batch done from round 0.
    """
    engine = engine_registry.get_engine(
        engine_registry.resolve_engine(cfg.engine, mode=cfg.mode,
                                       batch=queries.shape[0]))
    return engine.run(data, forest, A, params, queries, cfg, plan=plan,
                      live=live, live_sorted=live_sorted, n_active=n_active)


# ---------------------------------------------------------------------------
# (r,c)-ANN query (Alg. 4) — single fixed radius; used by tests/benchmarks
# ---------------------------------------------------------------------------

def rc_ann_query(data: jax.Array, forest: DEForest, A: jax.Array,
                 params: LSHParams, q: jax.Array, r: float,
                 cfg: QueryConfig) -> QueryResult:
    """Answer one (r,c)-ANN query (Alg. 4): returns the closest candidate
    found, or an invalid id (= n) when the algorithm would return nothing."""
    n = data.shape[0]
    cap = _auto_cap(n, params, cfg, forest)
    q_proj = project_query(q, A).reshape(params.L, params.K)
    ids, ok = range_query_round(forest, q_proj,
                                jnp.asarray(params.epsilon * r), cfg.M,
                                mode=cfg.mode, bounds_impl=cfg.bounds_impl,
                                probe_depth=cfg.probe_depth)
    d = exact_distances(data, q, ids, ok, impl=cfg.dist_impl)
    ids = jnp.where(ok, ids, n)
    cs = cand.merge_round(n, cand.init_state(n, cap), ids, d)
    best = jnp.argmin(cs.dists)
    t1 = cs.count >= jnp.asarray(params.beta * n + 1, jnp.int32)  # line 6
    t2 = jnp.sum(cs.dists <= params.c * r) >= 1                   # line 8
    give = t1 | t2
    out_id = jnp.where(give, cs.ids[best], n).astype(jnp.int32)
    out_d = jnp.where(give, cs.dists[best], jnp.inf)
    return QueryResult(ids=out_id[None], dists=out_d[None],
                       rounds=jnp.asarray(1, jnp.int32), n_candidates=cs.count,
                       final_r=jnp.asarray(r, jnp.float32))
