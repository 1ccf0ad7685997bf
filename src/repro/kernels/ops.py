"""jit'd public wrappers for the Pallas kernels.

Each wrapper pads inputs to hardware-aligned block multiples, dispatches to
the Pallas kernel (TPU) / interpret mode (CPU tests) / the pure-jnp reference
(dry-run lowering), and slices the padding back off.

Implementation selection:
  * explicit ``interpret=True``  -> Pallas in interpret mode (CPU-correct);
  * backend == 'tpu'             -> compiled Pallas kernel;
  * otherwise                    -> ``repro.kernels.ref`` oracle (pure XLA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import build_fused as _bf
from repro.kernels import lsh_project as _proj
from repro.kernels import encode_bins as _enc
from repro.kernels import leaf_bounds as _lb
from repro.kernels import l2_rerank as _l2
from repro.kernels import flash_attention as _fa
from repro.kernels import range_rerank as _rr


def _use_pallas(interpret: bool) -> bool:
    return interpret or jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def lsh_project(x, a, *, interpret: bool = False, block_n: int = 256):
    if not _use_pallas(interpret):
        return _ref.lsh_project(x, a)
    n, d = x.shape
    m = a.shape[1]
    xp = _pad_to(_pad_to(x, 0, block_n), 1, 128)
    ap = _pad_to(_pad_to(a, 0, 128), 1, 128)
    out = _proj.lsh_project(xp, ap, block_n=block_n, interpret=interpret)
    return out[:n, :m]


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def encode_bins(coords, breakpoints, *, interpret: bool = False,
                block_n: int = 512):
    if not _use_pallas(interpret):
        return _ref.encode_bins(coords, breakpoints)
    n = coords.shape[0]
    cp = _pad_to(coords, 0, block_n)
    out = _enc.encode_bins(cp, breakpoints, block_n=block_n,
                           interpret=interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("K", "L", "interpret",
                                             "block_n"))
def encode_pack(proj, breakpoints, *, K: int, L: int,
                interpret: bool = False, block_n: int = 512):
    """Fused encode + interleaved-key pack (build pipeline; see
    kernels/build_fused.py).  proj (n, L*K) -> per-tree layouts
    (proj_t, codes_t, key_hi, key_lo); rows padded to ``block_n`` (the
    build chunk size) and sliced back off."""
    if not _use_pallas(interpret):
        return _ref.encode_pack(proj, breakpoints, K=K, L=L)
    n = proj.shape[0]
    block_n = _build_block(block_n, interpret)
    pp = _pad_to(_pad_to(_dim_major(proj, L, K), 0, block_n), 1, 128)
    bp_t = _pad_to(_dim_major(breakpoints.T, L, K), 1, 128)
    codes, key_hi, key_lo = _bf.encode_pack(pp, bp_t, K=K, L=L,
                                            block_n=block_n,
                                            interpret=interpret)
    proj_t = proj.reshape(n, L, K).transpose(1, 0, 2)
    return proj_t, _per_tree(codes[:, :n], L, K), key_hi[:, :n], key_lo[:, :n]


@functools.partial(jax.jit, static_argnames=("K", "L", "interpret",
                                             "block_n"))
def project_encode_pack(x, a, breakpoints, *, K: int, L: int,
                        interpret: bool = False, block_n: int = 256):
    """One-pass project -> encode -> key-pack (the frozen-breakpoint seal
    path; see kernels/build_fused.py).  x (n, d), a (d, L*K) -> per-tree
    layouts; rows padded to ``block_n``, the feature dim to the 128-lane
    MXU width (zero padding preserves the projection)."""
    if not _use_pallas(interpret):
        return _ref.project_encode_pack(x, a, breakpoints, K=K, L=L)
    n = x.shape[0]
    block_n = _build_block(block_n, interpret)
    xp = _pad_to(_pad_to(x, 0, block_n), 1, 128)
    ap = _pad_to(_pad_to(_dim_major(a, L, K), 0, 128), 1, 128)
    bp_t = _pad_to(_dim_major(breakpoints.T, L, K), 1, 128)
    proj, codes, key_hi, key_lo = _bf.project_encode_pack(
        xp, ap, bp_t, K=K, L=L, block_n=block_n, interpret=interpret)
    return (_per_tree(proj[:, :n], L, K), _per_tree(codes[:, :n], L, K),
            key_hi[:, :n], key_lo[:, :n])


def _build_block(block_n: int, interpret: bool) -> int:
    """The build kernels put rows on the 128-lane axis: round the chunk up
    to a lane multiple for Mosaic (interpret mode takes any chunk)."""
    return block_n if interpret else -(-block_n // 128) * 128


def _dim_major(x: jax.Array, L: int, K: int) -> jax.Array:
    """Reorder the trailing (tree-major) L*K axis to dim-major K*L."""
    lead = x.shape[:-1]
    return jnp.swapaxes(x.reshape(*lead, L, K), -1, -2).reshape(*lead, K * L)


def _per_tree(rows: jax.Array, L: int, K: int) -> jax.Array:
    """(K*L, n) dim-major kernel rows -> the per-tree (L, n, K) layout."""
    return rows.reshape(K, L, -1).transpose(1, 2, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_l"))
def leaf_bounds(q, leaf_lo, leaf_hi, leaf_valid, breakpoints, *,
                interpret: bool = False, block_l: int = 256):
    """Leaf bounds take int16 (storage-dtype) bounds; the kernel consumes
    int32, so the cast happens here at use."""
    if not _use_pallas(interpret):
        return _ref.leaf_bounds(q, leaf_lo, leaf_hi, leaf_valid, breakpoints)
    nl = leaf_lo.shape[0]
    lo = _pad_to(leaf_lo.astype(jnp.int32), 0, block_l)
    hi = _pad_to(leaf_hi.astype(jnp.int32), 0, block_l)
    va = _pad_to(leaf_valid, 0, block_l, value=False)
    lb, ub = _lb.leaf_bounds(q, lo, hi, va, breakpoints, block_l=block_l,
                             interpret=interpret)
    return lb[:nl], ub[:nl]


@functools.partial(jax.jit, static_argnames=("interpret", "block_q", "block_c"))
def l2_rerank(q, c, *, interpret: bool = False, block_q: int = 128,
              block_c: int = 256):
    if not _use_pallas(interpret):
        return _ref.l2_rerank(q, c)
    b, m = q.shape[0], c.shape[0]
    qp = _pad_to(q, 0, block_q)
    cp = _pad_to(c, 0, block_c)
    out = _l2.l2_rerank(qp, cp, block_q=block_q, block_c=block_c,
                        interpret=interpret)
    return out[:b, :m]


@functools.partial(jax.jit, static_argnames=("leaf_size", "probe_depth",
                                             "interpret", "block_q",
                                             "block_l"))
def range_rerank(q, q_proj, r_eff, leaf_lo, leaf_hi, leaf_valid, breakpoints,
                 points, point_valid, live=None, *, leaf_size: int,
                 probe_depth: int = 0, interpret: bool = False,
                 block_q: int = 8, block_l: int = 8):
    """Fused batched range query + rerank; see kernels/range_rerank.py.

    ``r_eff`` is (B,) per-lane radii shared across trees, or (L, B) per-tree
    radii (the multi-probe engine passes pre-widened per-tree radii).  With
    ``probe_depth > 0`` and 1-D radii the wrapper widens them itself via
    :func:`repro.kernels.ref.probe_radii` so the probe_depth best near-miss
    leaves per (tree, lane) are admitted alongside the radius box.

    Pads the query batch to ``block_q`` (padded lanes get r_eff = -1 so they
    admit nothing), the leaf operands to ``block_l`` leaves (padded leaves
    invalid) and the feature dim to the 128-lane MXU width (zero padding
    preserves distances).  ``live`` is the optional (L, nl*leaf_size) per-point
    tombstone mask in sorted order (None = all live); dead points emit +inf
    inside the kernel tile, so deletes cost no extra pass.  Returns
    (L, B, nl*leaf_size).
    """
    if live is None:
        # pv & pv == pv: reusing the validity buffer as the live operand
        # keeps the all-live case allocation-free (no ones tensor).
        live = point_valid
    if probe_depth and r_eff.ndim == 1:
        r_eff = _ref.probe_radii(q_proj, leaf_lo.astype(jnp.int32),
                                 leaf_hi.astype(jnp.int32), leaf_valid,
                                 breakpoints, r_eff, probe_depth)
    if not _use_pallas(interpret):
        return _ref.range_rerank(q, q_proj, r_eff, leaf_lo, leaf_hi,
                                 leaf_valid, breakpoints, points, point_valid,
                                 live, leaf_size=leaf_size)
    L, B, K = q_proj.shape
    tile = block_l * leaf_size
    qp_b = _pad_to(_pad_to(q, 0, block_q), 1, 128)
    qproj_b = _pad_to(q_proj, 1, block_q)
    r2 = jnp.broadcast_to(r_eff, (L, B)) if r_eff.ndim == 1 else r_eff
    r_b = _pad_to(r2, 1, block_q, value=-1.0)[..., None]           # (L, B, 1)
    # Leaf bounding-box edge coordinates (the gather of ref.leaf_bounds,
    # done once per call) + validity, laid out per leaf block as rows.
    bp_t = jnp.swapaxes(breakpoints, 1, 2)                         # (L, E, K)
    E = bp_t.shape[1]

    def edge(idx):
        idx = jnp.clip(idx.astype(jnp.int32), 0, E - 1)
        return jnp.take_along_axis(bp_t, idx, axis=1)              # (L, nl, K)

    edges = jnp.concatenate(
        [edge(leaf_lo), edge(leaf_hi.astype(jnp.int32) + 1),
         leaf_valid.astype(jnp.float32)[..., None]], axis=2)       # (L, nl, 2K+1)
    edges = _pad_to(edges, 1, block_l)
    edges = jnp.swapaxes(edges.reshape(L, -1, block_l, 2 * K + 1), 2, 3)
    # The point rows are NOT padded to the leaf block: the kernel's last
    # point/output block may be ragged (its tail is masked off by pm_b),
    # which keeps a copy of the (L, n, d) points out of every round.
    pts_b = _pad_to(points, 2, 128)
    pm = (point_valid.astype(jnp.bool_) & live.astype(jnp.bool_))
    pm_b = _pad_to(pm.astype(jnp.float32), 1, tile).reshape(L, -1, 1, tile)
    out = _rr.range_rerank(qp_b, qproj_b, r_b, edges, pts_b, pm_b,
                           leaf_size=leaf_size, block_q=block_q,
                           block_l=block_l, interpret=interpret)
    return out[:, :B]


def range_rerank_heads(q, q_proj, r_eff, leaf_lo, leaf_hi, leaf_valid,
                       breakpoints, points, point_valid, live=None, *,
                       leaf_size: int, interpret: bool = False,
                       block_q: int = 8, block_l: int = 8):
    """Batched-*forest* fused range query + rerank (the KV-decode entry).

    Same contract as :func:`range_rerank` with one extra leading axis ``H``
    on every array argument: H independent forests (one per (batch,
    kv-head) in ``repro.decode``), each answering its own query batch.
    q (H, B, d); q_proj (H, L, B, K); r_eff (H, B); leaf arrays
    (H, L, nl, ...); points (H, L, nl*leaf_size, d).  Returns
    (H, L, B, nl*leaf_size).

    Implemented as ``jax.vmap`` over the single-forest wrapper: on CPU the
    ref oracle vmaps as plain XLA; on TPU the vmap lifts into a leading
    ``pallas_call`` grid dimension, so all H forests share one kernel
    launch instead of H dispatches.
    """
    if live is None:
        live = point_valid
    fn = functools.partial(range_rerank, leaf_size=leaf_size,
                           interpret=interpret, block_q=block_q,
                           block_l=block_l)
    return jax.vmap(fn)(q, q_proj, r_eff, leaf_lo, leaf_hi, leaf_valid,
                        breakpoints, points, point_valid, live)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128):
    """q (b, h, sq, dh), k/v (b, h, sk, dh) -> (b, h, sq, dh)."""
    if not _use_pallas(interpret):
        return _ref.flash_attention(q, k, v, causal=causal, scale=scale)
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    qp = _pad_to(_pad_to(q.reshape(b * h, sq, dh), 1, block_q), 2, 128)
    kp = _pad_to(_pad_to(k.reshape(b * h, sk, dh), 1, block_k), 2, 128)
    vp = _pad_to(_pad_to(v.reshape(b * h, sk, dh), 1, block_k), 2, 128)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k, sk_real=sk,
                              interpret=interpret)
    return out[:, :sq, :dh].reshape(b, h, sq, dh)
