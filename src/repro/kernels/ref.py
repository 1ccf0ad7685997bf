"""Pure-jnp oracles for every Pallas kernel.

These are the semantics of record: each kernel's test sweeps shapes/dtypes
and asserts allclose against the function here.  They are also the
implementations the multi-pod dry-run lowers (the CPU backend cannot compile
Mosaic/TPU custom calls), so they are written to be XLA-memory-sane
(blockwise attention never materializes the full score matrix).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp


def lsh_project(x: jax.Array, a: jax.Array) -> jax.Array:
    """(n, d) @ (d, m) -> (n, m) in f32 accumulation."""
    return jnp.dot(x, a, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def encode_bins(coords: jax.Array, breakpoints: jax.Array) -> jax.Array:
    """coords (n, D), breakpoints (D, Nr+1) -> region ids (n, D) int32.

    Region b = #(internal breakpoints <= x), clipped to [0, Nr-1]; identical
    to ``repro.core.encoding.encode``.
    """
    D, E = breakpoints.shape
    Nr = E - 1
    inner = breakpoints[:, 1:Nr]                         # (D, Nr-1)
    ge = coords[:, :, None] >= inner[None, :, :]         # (n, D, Nr-1)
    return jnp.clip(ge.sum(-1), 0, Nr - 1).astype(jnp.int32)


def encode_pack(proj: jax.Array, breakpoints: jax.Array, *, K: int,
                L: int) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused build pipeline oracle: encode + interleaved key-pack.

    proj (n, L*K), breakpoints (L*K, Nr+1) -> (proj_t (L, n, K) f32,
    codes_t (L, n, K) int32, key_hi (L, n) uint32, key_lo (L, n) uint32).
    Codes are identical to ``encode_bins``; key words are identical to
    ``repro.core.detree.interleave_keys`` per tree.
    """
    from repro.core.detree import interleave_keys
    n = proj.shape[0]
    # Same codes as ``encode_bins`` (tested), via the O(n D log Nr)
    # searchsorted form: this oracle IS the CPU build path, and the
    # kernel's O(Nr) compare-sweep formulation is an XLA memory/time hog
    # off-TPU (it materializes the (n, D, Nr-1) compare tensor).
    D, E = breakpoints.shape
    Nr = E - 1
    inner = breakpoints[:, 1:Nr]
    bins = jax.vmap(lambda e, col: jnp.searchsorted(e, col, side="right"),
                    in_axes=(0, 1), out_axes=1)(inner, proj)
    codes = jnp.clip(bins, 0, Nr - 1).astype(jnp.int32)  # (n, L*K)
    proj_t = proj.reshape(n, L, K).transpose(1, 0, 2)
    codes_t = codes.reshape(n, L, K).transpose(1, 0, 2)
    key_hi, key_lo = interleave_keys(codes_t, K)         # (L, n) each
    return proj_t, codes_t, key_hi, key_lo


def project_encode_pack(x: jax.Array, a: jax.Array, breakpoints: jax.Array,
                        *, K: int, L: int):
    """Projection-fused variant of :func:`encode_pack` (the frozen-
    breakpoint seal path): x (n, d), a (d, L*K) -> same outputs."""
    return encode_pack(lsh_project(x, a), breakpoints, K=K, L=L)


def leaf_bounds(q: jax.Array, leaf_lo: jax.Array, leaf_hi: jax.Array,
                leaf_valid: jax.Array,
                breakpoints: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fig. 5 LB/UB.  q (K,), leaf_lo/hi (nl, K) int32, bp (K, Nr+1)."""
    E = breakpoints.shape[1]

    def gather(idx):
        idx = jnp.clip(idx, 0, E - 1)
        return jax.vmap(lambda bk, ik: bk[ik], in_axes=(0, 1), out_axes=1)(
            breakpoints, idx)

    b_lo = gather(leaf_lo)
    # Widen at use even though ops.py already widens at the kernel boundary:
    # int16 leaf_hi would wrap at 32767 here, and this reference path is
    # also called directly by the equivalence tests.
    b_hi = gather(leaf_hi.astype(jnp.int32) + 1)
    d_lo = b_lo - q[None, :]
    d_hi = q[None, :] - b_hi
    lb_dim = jnp.maximum(jnp.maximum(d_lo, d_hi), 0.0)
    ub_dim = jnp.maximum(jnp.abs(q[None, :] - b_lo), jnp.abs(q[None, :] - b_hi))
    lb = jnp.sqrt((lb_dim * lb_dim).sum(-1))
    ub = jnp.sqrt((ub_dim * ub_dim).sum(-1))
    lb = jnp.where(leaf_valid, lb, jnp.inf)
    ub = jnp.where(leaf_valid, ub, jnp.inf)
    return lb, ub


def forest_leaf_lb(q_proj: jax.Array, leaf_lo: jax.Array, leaf_hi: jax.Array,
                   leaf_valid: jax.Array,
                   breakpoints: jax.Array) -> jax.Array:
    """Leaf LB distances for the whole forest at once.

    q_proj (L, B, K); leaf_lo/hi (L, nl, K); leaf_valid (L, nl);
    breakpoints (L, K, E) -> (L, B, nl) f32, +inf for invalid leaves.
    Radius-independent: the fused engine computes this once per batch and
    reuses it across rounds to rank probe candidates.
    """
    def per_tree(qp_t, lo_t, hi_t, lv_t, bp_t):
        return jax.vmap(
            lambda qp: leaf_bounds(qp, lo_t, hi_t, lv_t, bp_t)[0])(qp_t)

    return jax.vmap(per_tree)(q_proj, leaf_lo, leaf_hi,
                              leaf_valid.astype(jnp.bool_), breakpoints)


def probe_radii_from_lb(lb: jax.Array, r_eff: jax.Array,
                        probe_depth: int) -> tuple[jax.Array, jax.Array]:
    """Probe-widened admission radii from a leaf-LB table.

    lb (L, B, nl) leaf LBs (+inf for invalid leaves); r_eff (B,) radius per
    lane (-1 = done).  Per (tree, lane), widen the radius to also admit the
    ``probe_depth`` valid leaves with the smallest LB *above* r_eff — the
    near-miss leaves ranked by LB slack.  Done lanes keep r_eff = -1 and
    never probe.

    Returns (r_adm (L, B), probe_mask (L, B, nl)).  ``lb <= r_adm`` admits
    exactly the within-radius leaves plus the probe set (LB ties can admit
    a few more — a superset, which preserves the quality guarantees).  When
    a (tree, lane) has fewer than probe_depth near-miss leaves the k-th
    slack is +inf and every valid leaf is admitted.
    """
    L, B, nl = lb.shape
    outside = lb > r_eff[None, :, None]                # invalid leaves too
    slack = jnp.where(outside & jnp.isfinite(lb), lb, jnp.inf)
    depth = min(int(probe_depth), nl)
    kth = -jax.lax.top_k(-slack, depth)[0][..., -1]    # depth-th smallest
    # The depth-th probe leaf sits exactly ON the widened radius (r_adm is
    # its LB by construction), and the fused kernel recomputes leaf LBs
    # in-tile with a different accumulation order — a 1-ulp discrepancy
    # would silently drop the boundary leaf.  One relative-epsilon nudge
    # keeps it in; epsilon ties admit at most a few extra leaves (still a
    # superset, so the quality guarantees are untouched).
    kth = jnp.where(jnp.isfinite(kth), kth * (1 + 1e-5) + 1e-6, kth)
    r_adm = jnp.maximum(r_eff[None, :], kth)
    r_adm = jnp.where(r_eff[None, :] < 0, r_eff[None, :], r_adm)
    probe_mask = outside & jnp.isfinite(lb) & (lb <= r_adm[..., None])
    return r_adm, probe_mask


def probe_radii(q_proj: jax.Array, leaf_lo: jax.Array, leaf_hi: jax.Array,
                leaf_valid: jax.Array, breakpoints: jax.Array,
                r_eff: jax.Array, probe_depth: int) -> jax.Array:
    """Convenience composition: leaf-LB table -> probe-widened (L, B) radii."""
    lb = forest_leaf_lb(q_proj, leaf_lo, leaf_hi, leaf_valid, breakpoints)
    return probe_radii_from_lb(lb, r_eff, probe_depth)[0]


def l2_rerank(q: jax.Array, c: jax.Array) -> jax.Array:
    """Exact Euclidean distances: q (b, d), c (m, d) -> (b, m)."""
    qq = (q.astype(jnp.float32) ** 2).sum(-1, keepdims=True)      # (b, 1)
    cc = (c.astype(jnp.float32) ** 2).sum(-1)[None, :]            # (1, m)
    qc = jnp.dot(q, c.T, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    return jnp.sqrt(jnp.maximum(qq - 2.0 * qc + cc, 0.0))


def range_rerank(q: jax.Array, q_proj: jax.Array, r_eff: jax.Array,
                 leaf_lo: jax.Array, leaf_hi: jax.Array,
                 leaf_valid: jax.Array, breakpoints: jax.Array,
                 points: jax.Array, point_valid: jax.Array,
                 live: jax.Array | None = None, *,
                 leaf_size: int, probe_depth: int = 0) -> jax.Array:
    """Fused batched range query + exact rerank (semantics of record).

    q (B, d); q_proj (L, B, K); r_eff projected admission radii — either
    (B,) shared across trees or (L, B) per-tree (-1 = inactive lane);
    leaf_lo/hi (L, nl, K); leaf_valid (L, nl); breakpoints (L, K, E);
    points (L, nl*leaf_size, d) code-sorted original-space points;
    point_valid (L, nl*leaf_size); live (L, nl*leaf_size) per-point
    tombstone mask in sorted order (None = all live).

    With probe_depth > 0 and 1-D r_eff the radii are first widened per
    (tree, lane) via :func:`probe_radii` so the ``probe_depth`` nearest
    near-miss leaves are admitted too (multi-probe rounds).

    Returns (L, B, nl*leaf_size) f32: the exact original-space distance for
    every live point whose covering leaf has LB <= r_eff (leaf-granular
    admission, paper §VI-B2 opt. #1, *without* a top-M cut), +inf elsewhere.
    """
    if live is None:
        live = jnp.ones_like(point_valid)
    L = q_proj.shape[0]
    B = q_proj.shape[1]
    if probe_depth and r_eff.ndim == 1:
        r_eff = probe_radii(q_proj, leaf_lo, leaf_hi, leaf_valid,
                            breakpoints, r_eff, probe_depth)
    r2 = jnp.broadcast_to(r_eff, (L, B)) if r_eff.ndim == 1 else r_eff

    def per_tree(qp_t, r_t, lo_t, hi_t, lv_t, bp_t, pts_t, pv_t, lm_t):
        lb, _ = jax.vmap(
            lambda qp: leaf_bounds(qp, lo_t, hi_t, lv_t, bp_t))(qp_t)
        admit = (lb <= r_t[:, None]) & lv_t[None, :]         # (B, nl)
        dist = l2_rerank(q, pts_t)                           # (B, nl*ls)
        mask = jnp.repeat(admit, leaf_size, axis=1) & (pv_t & lm_t)[None, :]
        return jnp.where(mask, dist, jnp.inf)

    return jax.vmap(per_tree)(q_proj, r2, leaf_lo, leaf_hi,
                              leaf_valid.astype(jnp.bool_), breakpoints,
                              points, point_valid.astype(jnp.bool_),
                              live.astype(jnp.bool_))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False, scale: float | None = None,
                    block_k: int = 512) -> jax.Array:
    """Blockwise (online-softmax) attention — never materializes (sq, sk).

    q (b, h, sq, dh); k/v (b, h, sk, dh).  This is both the oracle for the
    Pallas kernel and the XLA implementation the dry-run compiles.
    """
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    qf = (q * scale).astype(jnp.float32)
    nblk = -(-sk // block_k)
    pad = nblk * block_k - sk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kp.reshape(b, h, nblk, block_k, dh)
    vb = vp.reshape(b, h, nblk, block_k, dh)
    kpos = jnp.arange(nblk * block_k).reshape(nblk, block_k)
    qpos = jnp.arange(sq)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kblk, vblk, kp_blk = inp                     # (b,h,bk,dh) etc.
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk.astype(jnp.float32))
        mask = kp_blk[None, :] < sk                  # padding
        if causal:
            mask = mask & (kp_blk[None, :] <= qpos[:, None])
        s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
        m_cur = jnp.maximum(m_prev, s.max(-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32))
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (kb.transpose(2, 0, 1, 3, 4), vb.transpose(2, 0, 1, 3, 4), kpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def attention_reference(q, k, v, *, causal=False, scale=None):
    """Naive softmax attention (materializes scores) — oracle's oracle."""
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    scale = scale if scale is not None else 1.0 / (dh ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
