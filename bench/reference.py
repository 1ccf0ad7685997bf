"""The plain reference the benchmark's ``correct`` is decided against.

Nothing here imports the program (``repro``) or takes anything it made
except the answers under test: the exact k-NN is brute force over the base
vectors, in blocks of queries, with f32 products at ``HIGHEST`` precision
(a TPU rounds f32 matmul operands to bf16 by default); the forest checks
recompute the projection from the build key by the paper's sampling
(``A ~ N(0, 1)``, Eq. 1), the breakpoints from the build key's sample by
the paper's equi-depth rule (Alg. 1) and the interleaved sort key from the
codes.

``operands`` is the control's switch: the same brute force with its
matmul operands in bfloat16 (what one default-precision pass on a TPU
computes) is the lower-precision stand-in the comparison must refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b, operands=jnp.float32):
    """a @ b with f32 accumulation: exact f32 products (``HIGHEST``) for
    the reference, or bfloat16 operands for the control."""
    if operands == jnp.float32:
        return jnp.dot(a, b, precision=HIGHEST,
                       preferred_element_type=jnp.float32)
    return jnp.dot(a.astype(operands), b.astype(operands),
                   preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "operands"))
def _topk_block(q, data, sq_norms, *, k, operands):
    d2 = (jnp.sum(q * q, 1, keepdims=True)
          - 2.0 * matmul(q, data.T, operands) + sq_norms[None, :])
    neg, ids = jax.lax.top_k(-d2, k)
    return ids, jnp.sqrt(jnp.maximum(-neg, 0.0))


@jax.jit
def _sq_norms(data):
    return jnp.sum(data * data, axis=1)


def exact_topk(data, queries: np.ndarray, k: int, *, block: int = 128,
               operands=jnp.float32):
    """Brute-force L2 top-k of host ``queries`` over device ``data``.
    Returns numpy (ids (m, k) int32, dists (m, k) f32)."""
    m = len(queries)
    sq = _sq_norms(data)
    pad = (-m) % block
    qs = np.concatenate([queries, np.zeros((pad, queries.shape[1]),
                                           np.float32)])
    ids, dists = [], []
    for i in range(0, len(qs), block):
        bi, bd = _topk_block(jnp.asarray(qs[i:i + block]), data, sq, k=k,
                             operands=operands)
        ids.append(bi)
        dists.append(bd)
    return (np.concatenate([np.asarray(b) for b in ids])[:m],
            np.concatenate([np.asarray(b) for b in dists])[:m])


@jax.jit
def _pair_dist(q, ids, data):
    rows = jnp.take(data, jnp.clip(ids, 0, data.shape[0] - 1), axis=0)
    return jnp.sqrt(jnp.sum((rows - q[:, None, :]) ** 2, axis=-1))


def pair_distances(data, queries: np.ndarray, ids: np.ndarray, *,
                   block: int = 1024) -> np.ndarray:
    """||q_i - x_{ids[i, j]}|| by direct differences (no matmul rounding).
    Out-of-range ids are clipped; the caller flags them separately."""
    m = len(queries)
    pad = (-m) % block
    qs = np.concatenate([queries, np.zeros((pad, queries.shape[1]),
                                           np.float32)])
    ii = np.concatenate([ids, np.zeros((pad, ids.shape[1]), ids.dtype)])
    out = [_pair_dist(jnp.asarray(qs[i:i + block]),
                      jnp.asarray(ii[i:i + block]), data)
           for i in range(0, len(qs), block)]
    return np.concatenate([np.asarray(o) for o in out])[:m]


def compare_answers(ids: np.ndarray, dists: np.ndarray, gt_ids: np.ndarray,
                    gt_dists: np.ndarray, ref_dists: np.ndarray, *, n: int,
                    c: float) -> dict:
    """The numbers ``correct`` compares for answered k-NN queries.

    ids/dists: what the timed path returned, (m, k); gt_*: the exact
    top-k; ref_dists: the reference's distance from each query to each
    returned id.  Returns

      malformed      answers with an id outside [0, n), a repeated id, a
                     non-finite distance or distances out of order
      dist_rel_err   max |returned - reference| / reference distance
      miss_rate      1 - recall@k against the exact top-k
      c2_fail_share  share of answers whose i-th distance exceeds c^2 times
                     the exact i-th distance for some i
    """
    k = gt_ids.shape[1]
    bad_id = (ids < 0) | (ids >= n)
    srt = np.sort(ids, axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    with np.errstate(invalid="ignore"):          # inf - inf in empty lanes
        order = (np.diff(dists, axis=1) < 0).any(axis=1)
    nonfinite = ~np.isfinite(dists).all(axis=1)
    malformed = bad_id.any(axis=1) | dup | order | nonfinite
    ok = ~malformed
    if ok.any():
        err = np.abs(dists[ok] - ref_dists[ok]) / np.maximum(
            ref_dists[ok], np.finfo(np.float32).tiny)
        dist_rel_err = float(err.max())
        c2_fail = (ref_dists[ok] > (c * c) * gt_dists[ok] * (1 + 1e-6)).any(1)
        c2_fail_share = float(c2_fail.mean())
    else:
        dist_rel_err, c2_fail_share = float("inf"), 1.0
    hits = np.array([len(np.intersect1d(a[:k], b)) for a, b in
                     zip(np.where(bad_id, -1, ids), gt_ids)], np.float64)
    return {"malformed": int(malformed.sum()),
            "dist_rel_err": dist_rel_err,
            "miss_rate": float(1.0 - hits.mean() / k),
            "c2_fail_share": c2_fail_share}


# ---------------------------------------------------------------------------
# Forest checks (the build cell)
# ---------------------------------------------------------------------------

def projection_matrix(key, d: int, K: int, L: int):
    """The paper's p-stable family drawn from a build key: ``key`` splits
    into (projection, breakpoint-sample) keys; A ~ N(0, 1)^(d x LK)."""
    kp, _ = jax.random.split(key)
    return jax.random.normal(kp, (d, L * K), jnp.float32)


def breakpoint_sample(key, n: int, *, fraction: float = 0.1,
                      least: int = 4096):
    """Rows whose projections set the breakpoints: n_s = 0.1 n of them
    (the paper's sample, at least 4096), drawn without replacement with
    the build key's second half; every row where n_s reaches n."""
    n_s = min(n, max(least, int(n * fraction)))
    if n_s == n:
        return jnp.arange(n)
    _, kb = jax.random.split(key)
    return jax.random.choice(kb, n, (n_s,), replace=False)


def equi_depth_breakpoints(proj, sample, Nr: int):
    """Per-dimension breakpoints (D, Nr + 1) of projections (n, D): B(1)
    and B(Nr + 1) the min and max over all rows, B(z) the sample's
    order statistic floor(m / Nr) * (z - 1) for z = 2..Nr (paper §III-A),
    made non-decreasing."""
    s = jnp.sort(proj[sample], axis=0)
    m = s.shape[0]
    inner = s[jnp.clip(jnp.arange(1, Nr) * (m // Nr), 0, m - 1)]
    bp = jnp.concatenate([proj.min(0, keepdims=True), inner,
                          proj.max(0, keepdims=True)]).T
    return jax.lax.cummax(bp, axis=1)


def interleaved_key(codes, K: int):
    """MSB-first round-robin bit interleave of K 8-bit codes, cut to its
    first 64 bits, as two 32-bit words (hi, lo); lo is 0 for K <= 4.
    Configurations keep 32 % K == 0, where this is the whole split order
    that fits 64 bits."""
    bits = []
    for b in range(7, -1, -1):
        for k in range(K):
            bits.append((codes[..., k].astype(jnp.uint32) >> b) & 1)
    hi_bits, lo_bits = bits[:32], bits[32:64]

    def pack(bs):
        word = jnp.zeros(codes.shape[:-1], jnp.uint32)
        for bit in bs:
            word = (word << 1) | bit
        return word << (32 - len(bs)) if bs else word
    return pack(hi_bits), pack(lo_bits)


@functools.partial(jax.jit, static_argnames=("n", "K", "leaf_size"))
def _forest_errors(data, A_ref, sample, A, point_ids, valid, proj_sorted,
                   codes_sorted, leaf_lo, leaf_hi, leaf_valid, breakpoints,
                   points_sorted, *, n, K, leaf_size):
    L, n_pad = point_ids.shape
    Nr = breakpoints.shape[2] - 1
    errs = {}
    errs["projection_not_as_sampled"] = jnp.sum(A != A_ref)
    # each tree's ids are a permutation of 0..n-1 on its valid rows
    ids = jnp.where(valid, point_ids, n)
    seen = jax.vmap(lambda r: jnp.zeros((n + 1,), jnp.int32).at[r].add(1))(ids)
    errs["not_a_permutation"] = jnp.sum(seen[:, :n] != 1) + jnp.sum(
        valid != (jnp.arange(n_pad) < n)[None, :])
    safe = jnp.clip(point_ids, 0, n - 1)
    # projections against the reference projection of the same rows
    proj_ref = matmul(data, A_ref)                               # (n, LK)
    proj_ref = proj_ref.reshape(n, L, K).transpose(1, 0, 2)     # (L, n, K)
    want = jnp.take_along_axis(proj_ref, safe[..., None], axis=1)
    scale = jnp.sqrt(jnp.mean(proj_ref ** 2, axis=1, keepdims=True))
    rel = jnp.abs(proj_sorted - want) / scale
    proj_err = jnp.max(jnp.where(valid[..., None], rel, 0.0))
    # breakpoints against the reference's, from the same sample rows
    bp_ref = equi_depth_breakpoints(matmul(data, A_ref), sample, Nr)
    bp_ref = bp_ref.reshape(L, K, Nr + 1)
    bp_err = jnp.max(jnp.abs(breakpoints - bp_ref)
                     / scale.transpose(0, 2, 1))
    # codes are the regions of the stored projections
    c = codes_sorted.astype(jnp.int32)
    lo_edge = jnp.take_along_axis(breakpoints.transpose(0, 2, 1), c, axis=1)
    hi_edge = jnp.take_along_axis(breakpoints.transpose(0, 2, 1),
                                  jnp.minimum(c + 1, Nr), axis=1)
    p = proj_sorted
    below = (c >= 1) & (p < lo_edge)
    above = (c <= Nr - 2) & (p >= hi_edge)
    errs["code_not_region"] = jnp.sum((below | above | (c < 0) | (c >= Nr))
                                      & valid[..., None])
    # rows sorted by the interleaved key of their codes
    hi, lo = interleaved_key(c, K)
    dec = (hi[:, 1:] < hi[:, :-1]) | ((hi[:, 1:] == hi[:, :-1])
                                      & (lo[:, 1:] < lo[:, :-1]))
    errs["key_order"] = jnp.sum(dec & valid[:, 1:])
    # leaf summaries: per-dimension min/max code of the leaf's valid rows
    blocks = c.reshape(L, -1, leaf_size, K)
    bmask = valid.reshape(L, -1, leaf_size)[..., None]
    big = jnp.iinfo(jnp.int32).max
    want_lo = jnp.where(bmask, blocks, big).min(axis=2)
    want_hi = jnp.where(bmask, blocks, -1).max(axis=2)
    lv = bmask[..., 0].any(axis=2)
    errs["leaf_bounds"] = (
        jnp.sum((leaf_lo.astype(jnp.int32) != want_lo) & lv[..., None])
        + jnp.sum((leaf_hi.astype(jnp.int32) != want_hi) & lv[..., None])
        + jnp.sum(leaf_valid != lv))
    # the search layout: sorted point rows are the data rows they name
    # (one tree at a time: a gathered (L, n, d) copy would not fit beside
    # the layout at n = 1M)
    def tree_rows(args):
        pts, idx, ok = args
        rows = jnp.take(data, idx, axis=0)
        return jnp.sum(jnp.any(pts[:n_pad] != rows, axis=-1) & ok)
    errs["layout_rows"] = jnp.sum(jax.lax.map(
        tree_rows, (points_sorted, safe, valid)))
    return errs, proj_err, bp_err


def forest_numbers(data, build_key, *, K: int, L: int, leaf_size: int,
                   A, point_ids, valid, proj_sorted, codes_sorted, leaf_lo,
                   leaf_hi, leaf_valid, breakpoints, points_sorted) -> dict:
    """Structure errors (an exact count), and the relative errors of the
    stored projections and of the breakpoints, of a built forest against
    the reference's."""
    n, d = data.shape
    A_ref = projection_matrix(build_key, d, K, L)
    sample = breakpoint_sample(build_key, n)
    errs, proj_err, bp_err = _forest_errors(
        data, A_ref, sample, A, point_ids, valid, proj_sorted, codes_sorted, leaf_lo,
        leaf_hi, leaf_valid, breakpoints, points_sorted, n=n, K=K,
        leaf_size=leaf_size)
    parts = {k: int(v) for k, v in errs.items()}
    return {"structure_errors": sum(parts.values()),
            "proj_rel_err": float(proj_err),
            "breakpoint_rel_err": float(bp_err), "structure_parts": parts}
