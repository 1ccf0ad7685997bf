"""Pallas kernel: fused batched range query + exact rerank (one-pass).

The seed query round was three HBM round-trips per query per tree: leaf LB
pruning (``leaf_bounds``), candidate gather, then exact rerank
(``l2_rerank``).  This kernel fuses all of them into one grid pass per
(query-block, leaf-block) tile:

  1. leaf LB distances from the leaf bounding-box edge coordinates (VPU —
     same formulation as ``leaf_bounds``; the wrapper gathers the edges
     once per call, so the kernel never indexes the breakpoint table);
  2. radius admission  LB <= r_eff[q]  (per-lane radii; a *done* query lane
     carries r_eff = -1 and admits nothing — the active-lane mask costs no
     extra input);
  3. the "gather" is free: leaves are contiguous blocks of the code-sorted
     point array, so the leaf-block grid index *is* the candidate gather;
  4. exact original-space distances of the (block_q, d) query tile against
     the (block_l*leaf_size, d) point tile on the MXU, masked to +inf
     outside admitted leaves.

Leaf summaries and sorted points therefore stream through VMEM once per
query *block* instead of once per query.  Admission is leaf-granular
(paper §VI-B2 optimization #1) without the seed's top-M truncation: every
leaf whose LB passes the radius contributes, which admits a superset of the
strict Alg. 3 rule and preserves the quality guarantees
(docs/DESIGN.md §3).

Grid: (L, B/block_q, nl/block_l) — the tree axis rides the grid, so one
pallas_call serves the whole forest.  When every lane of a query tile is
inactive (or no leaf is admitted) the MXU work is skipped via ``pl.when``.

Mosaic layout: every block's last two dims are (8, 128)-aligned or span
the whole array dim.  Per-leaf and per-point rows therefore arrive as
rank-4 ``(L, nl/block_l, rows, width)`` operands whose trailing block is
the whole ``(rows, width)`` tile, radii as an ``(L, B, 1)`` column, and
masks are f32 (Mosaic cannot cast vectors of i1 to wider types).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The rerank is an *exact* distance: f32 operands keep f32 products on the
# MXU (the TPU default would round them to bf16 first).
PRECISION = jax.lax.Precision.HIGHEST


def _kernel(q_ref, qp_ref, r_ref, edge_ref, pts_ref, pm_ref, o_ref, *,
            K: int, leaf_size: int):
    qp = qp_ref[0]                                     # (bq, K) f32
    r_eff = r_ref[0]                                   # (bq, 1) f32; -1 = done
    edges = edge_ref[0, 0]                             # (2K+1, bl) f32

    # LB distance per (query, leaf): accumulate per-dimension clamped gaps.
    # K is small and static — unrolled 2D VPU ops, no (bq, bl, K) tensor.
    acc = jnp.zeros((qp.shape[0], edges.shape[1]), jnp.float32)
    for k in range(K):
        d_lo = edges[k:k + 1, :] - qp[:, k:k + 1]          # (bq, bl)
        d_hi = qp[:, k:k + 1] - edges[K + k:K + k + 1, :]
        t = jnp.maximum(jnp.maximum(d_lo, d_hi), 0.0)
        acc = acc + t * t
    lb = jnp.sqrt(acc)

    valid = edges[2 * K:2 * K + 1, :]                  # (1, bl) 1.0 / 0.0
    admit = jnp.where((lb <= r_eff) & (valid > 0.0), 1.0, 0.0)  # (bq, bl)
    any_admitted = jnp.max(admit) > 0.0

    inf = jnp.float32(jnp.inf)

    @pl.when(any_admitted)
    def _compute():
        q = q_ref[...].astype(jnp.float32)             # (bq, d)
        pts = pts_ref[0].astype(jnp.float32)           # (bl*ls, d)
        qq = jnp.sum(q * q, axis=1, keepdims=True)
        pp = jnp.sum(pts * pts, axis=1)[None, :]
        qc = jax.lax.dot_general(q, pts, (((1,), (1,)), ((), ())),
                                 precision=PRECISION,
                                 preferred_element_type=jnp.float32)
        dist = jnp.sqrt(jnp.maximum(qq - 2.0 * qc + pp, 0.0))
        mask = jnp.repeat(admit, leaf_size, axis=1) * pm_ref[0, 0]
        o_ref[0] = jnp.where(mask > 0.0, dist, inf)

    @pl.when(jnp.logical_not(any_admitted))
    def _skip():
        o_ref[0] = jnp.full(o_ref.shape[1:], inf, jnp.float32)


def range_rerank(q: jax.Array, q_proj: jax.Array, r_eff: jax.Array,
                 leaf_edges: jax.Array, points: jax.Array,
                 point_mask: jax.Array, *,
                 leaf_size: int, block_q: int = 8, block_l: int = 8,
                 interpret: bool = False) -> jax.Array:
    """Fused range query + rerank over all L trees.

    q (B, d) original-space queries; q_proj (L, B, K); r_eff (L, B, 1)
    per-(tree, lane) projected admission radii (eps*r broadcast over trees
    for plain radius rounds; per-tree probe-widened radii for multi-probe
    rounds; -1 for done lanes); leaf_edges (L, nl/block_l, 2K+1, block_l)
    f32 — per leaf, its bounding-box lower edge coordinates (K rows), upper
    edge coordinates (K rows) and validity (1.0 / 0.0); points
    (L, npts, d) code-sorted original-space points; point_mask
    (L, nl/block_l, 1, block_l*ls) f32 — 1.0 where the point is valid and
    live, in sorted order.

    Returns (L, B, npts) f32 with npts = points.shape[1]: exact distance
    where the covering leaf is admitted at radius r_eff and the point is
    valid and live, +inf elsewhere.  B must be a block_q multiple and the
    leaf operands cover nl/block_l whole leaf blocks (ops.py pads and lays
    them out); npts may end inside the last leaf block — that ragged tail
    block reads unspecified point rows, which point_mask zeroes, and its
    out-of-range writes are dropped.
    """
    L, B, K = q_proj.shape
    d = q.shape[1]
    nb = leaf_edges.shape[1]
    npts = points.shape[1]
    assert B % block_q == 0, (B, block_q)
    assert leaf_edges.shape == (L, nb, 2 * K + 1, block_l), leaf_edges.shape
    assert points.shape[0] == L and (nb - 1) * block_l * leaf_size < npts \
        <= nb * block_l * leaf_size, (points.shape, L, nb, block_l)
    assert point_mask.shape == (L, nb, 1, block_l * leaf_size), \
        point_mask.shape
    assert r_eff.shape == (L, B, 1), (r_eff.shape, L, B)
    grid = (L, B // block_q, nb)
    tile = block_l * leaf_size
    return pl.pallas_call(
        lambda *refs: _kernel(*refs, K=K, leaf_size=leaf_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda l, i, j: (i, 0)),
            pl.BlockSpec((1, block_q, K), lambda l, i, j: (l, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda l, i, j: (l, i, 0)),
            pl.BlockSpec((1, 1, 2 * K + 1, block_l),
                         lambda l, i, j: (l, j, 0, 0)),
            pl.BlockSpec((1, tile, d), lambda l, i, j: (l, j, 0)),
            pl.BlockSpec((1, 1, 1, tile), lambda l, i, j: (l, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, tile), lambda l, i, j: (l, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, B, npts), jnp.float32),
        interpret=interpret,
    )(q, q_proj, r_eff, leaf_edges, points, point_mask)
