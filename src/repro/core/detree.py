"""DE-Tree / DE-Forest (paper §III-B, Alg. 2) — TPU-native array form.

A DE-Tree organizes iSAX-encoded points so that range queries can prune via
per-node lower/upper-bound distances (paper Fig. 5).  Pointer-based trees do
not map to TPUs, so we store each tree as a *code-sorted array*:

  * points are sorted by the bit-interleaved (MSB-first, round-robin) iSAX
    code — exactly the order a DE-Tree's recursive binary splits induce, so a
    contiguous block of the sorted array corresponds to a subtree;
  * leaves are fixed-size blocks of ``leaf_size`` consecutive sorted points;
  * each leaf stores its per-dimension region interval [lo, hi] (the node's
    bounding iSAX prefix, tightened to the actual occupied regions).

LB/UB distances computed from a leaf's [lo, hi] intervals and the breakpoint
coordinates are identical in form to the paper's Fig. 5 bounds and remain
admissible (LB <= true projected distance <= UB for every point in the leaf;
property-tested), so all pruning/guarantee arguments carry over.

All L trees are built in one shot (vectorized over the leading L axis) — the
PDET-LSH parallel build (Alg. 7) falls out of data sharding: each device
builds a complete local forest over its shard (see ``core.distributed``).

Build pipeline (docs/DESIGN.md §8).  The hot path is the *fused, single-sort*
builder: the ``kernels/build_fused.py`` Pallas kernel streams row chunks of
the input through project -> encode -> key-pack in one grid pass, emitting
per-tree layouts directly (no (n, L*K) intermediates or transposed copies),
then ONE stable variadic sort per forest (``code_sort_orders``) orders all L
trees at once.  The two packed uint32 key words compared lexicographically
ARE the 64-bit interleaved key — an x64-safe uint64 — and for K <= 4 the
whole key fits the hi word and the low word is statically dropped.  The
stable (hi, lo) sort produces the *identical* permutation as the seed's
double stable argsort (stable radix argument; property-tested in
tests/test_build_fused.py), so fused-built forests are bit-identical to
reference-built ones.  ``build_impl='reference'`` keeps the seed per-tree
path as the semantics-of-record oracle and the benchmark baseline.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import encoding as enc

# Storage dtypes of the code-side index arrays (docs/DESIGN.md §8): region
# ids are 8-bit symbols (Nr <= 256) and leaf bounds are small region
# indices, so the resident index keeps them narrow — uint8 codes, int16
# bounds — and every consumer casts at use (the kernels' ops wrappers
# widen to int32 on entry).
CODE_DTYPE = jnp.uint8
LEAF_DTYPE = jnp.int16
MAX_NR = 256          # uint8 code storage: region ids must fit [0, 255]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DEForest:
    """L DE-Trees over one (shard of a) dataset, in array form."""

    point_ids: jax.Array     # (L, n_pad) int32 — original index; n = padding
    proj_sorted: jax.Array   # (L, n_pad, K) f32 — projected coords, sorted order
    codes_sorted: jax.Array  # (L, n_pad, K) uint8 — region ids, sorted order
    valid: jax.Array         # (L, n_pad) bool
    leaf_lo: jax.Array       # (L, n_leaves, K) int16 — occupied region interval
    leaf_hi: jax.Array       # (L, n_leaves, K) int16
    leaf_valid: jax.Array    # (L, n_leaves) bool
    breakpoints: jax.Array   # (L, K, Nr+1) f32
    n: int = dataclasses.field(metadata=dict(static=True))
    leaf_size: int = dataclasses.field(metadata=dict(static=True))

    @property
    def L(self) -> int:
        return self.point_ids.shape[0]

    @property
    def K(self) -> int:
        return self.breakpoints.shape[1]

    @property
    def n_leaves(self) -> int:
        return self.leaf_lo.shape[1]

    @property
    def Nr(self) -> int:
        return self.breakpoints.shape[2] - 1

    def size_bytes(self) -> int:
        """Resident code-side footprint (actual dtypes: codes 1B, ids 4B,
        bounds 2B, breakpoints 4B — proj_sorted excluded, as in the paper's
        index-size accounting)."""
        return int(sum(a.size * a.dtype.itemsize
                       for a in (self.codes_sorted, self.point_ids,
                                 self.leaf_lo, self.leaf_hi,
                                 self.breakpoints)))


# ---------------------------------------------------------------------------
# Interleaved sort keys
# ---------------------------------------------------------------------------

def key_bit_budget(K: int) -> tuple[int, int, int]:
    """(bits_per_dim, hi_bits, lo_bits) of the interleaved key for K dims.

    Up to 64 total bits split over two uint32 words; deeper bits than 64/K
    per dim do not affect leaf grouping materially.  For K <= 4 the whole
    key fits the hi word (lo_bits == 0) and the sort drops the low word
    statically.
    """
    bits_total = min(8, max(1, 64 // K))     # bits per dim that fit 2 words
    hi_bits = min(bits_total, max(1, 32 // K))
    return bits_total, hi_bits, bits_total - hi_bits


def interleave_keys(codes: jax.Array, K: int) -> tuple[jax.Array, jax.Array]:
    """Bit-interleaved sort keys from (..., K) region ids in [0, 256).

    Returns (key_hi, key_lo) uint32 of shape ``codes.shape[:-1]``: MSB-first,
    round-robin over dimensions — the linearization of the DE-Tree's split
    order ("each split performs a binary refinement on a single dimension",
    §III-B).  The (hi, lo) pair compared lexicographically is the packed
    64-bit key.  Fully vectorized (one shift/mask/sum over a (nbits, K)
    weight table — no per-bit Python loop), batches over any leading axes,
    and produces bit-identical words to the seed per-bit packing.
    """
    _, hi_bits, lo_bits = key_bit_budget(K)

    def pack(start_bit: int, nbits: int) -> jax.Array:
        if nbits == 0:
            return jnp.zeros(codes.shape[:-1], dtype=jnp.uint32)
        shift = jnp.arange(7 - start_bit, 7 - start_bit - nbits, -1,
                           dtype=jnp.uint32)                   # (nbits,)
        # Bit level b of dim j lands at position nbits*K - 1 - (b*K + j);
        # positions >= 32 overflow the word and are dropped *explicitly*
        # (weights built host-side at trace time), not via backend
        # shift-overflow behavior — the compactor's host keys mirror this.
        import numpy as _np
        pos = (nbits * K - 1
               - (_np.arange(nbits)[:, None] * K + _np.arange(K)[None, :]))
        weight = jnp.asarray(
            _np.where(pos < 32,
                      _np.uint64(1) << _np.minimum(pos, 31).astype(_np.uint64),
                      0).astype(_np.uint32))                   # (nbits, K)
        bits = (codes[..., None, :].astype(jnp.uint32)
                >> shift[:, None]) & jnp.uint32(1)             # (..., nbits, K)
        return jnp.sum(bits * weight, axis=(-2, -1), dtype=jnp.uint32)

    return pack(0, hi_bits), pack(hi_bits, lo_bits)


def _interleave_keys(codes: jax.Array, K: int) -> tuple[jax.Array, jax.Array]:
    """Seed-compatible alias of :func:`interleave_keys` ((n, K) -> (n,))."""
    return interleave_keys(codes, K)


def code_sort_orders(key_hi: jax.Array, key_lo: jax.Array,
                     K: int) -> jax.Array:
    """Sorting permutations for every tree from (L, n) packed key words.

    ONE stable variadic sort (``lax.sort`` with the two key words compared
    lexicographically — i.e. a 64-bit key compare — and an iota payload that
    becomes the permutation) replaces the seed's two stable argsorts per
    tree; all L trees sort in the same call (batched over the leading axis).
    Stability makes the permutation identical to the seed composition
    "stable-by-lo then stable-by-hi" (radix argument, property-tested).

    Off-trace on the CPU backend the sort runs as numpy's stable
    ``lexsort`` (radix on integer keys, ~5x faster than XLA CPU's
    comparator sort; the permutation is identical — both are the stable
    lexicographic (hi, lo) order), mirroring ``encoding._sort_columns``.
    """
    if (not isinstance(key_hi, jax.core.Tracer)
            and jax.default_backend() == "cpu"):
        import numpy as _np
        hi = _np.asarray(key_hi)
        lo = _np.asarray(key_lo)
        order = _np.empty(hi.shape, _np.int32)
        for l in range(hi.shape[0]):        # lexsort: last key is primary
            order[l] = _np.lexsort((lo[l], hi[l]))
        return jnp.asarray(order)
    n = key_hi.shape[-1]
    iota = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), key_hi.shape)
    if key_bit_budget(K)[2] == 0:       # key fits one word: drop the low one
        _, order = jax.lax.sort((key_hi, iota), dimension=-1,
                                is_stable=True, num_keys=1)
    else:
        _, _, order = jax.lax.sort((key_hi, key_lo, iota), dimension=-1,
                                   is_stable=True, num_keys=2)
    return order


def _sort_by_code(codes: jax.Array, K: int) -> jax.Array:
    """Seed path: permutation sorting (n, K) codes by interleaved key via
    two stable argsorts.  Kept as the semantics-of-record oracle for the
    single-sort equivalence property tests (and ``build_impl='reference'``).
    """
    key_hi, key_lo = interleave_keys(codes, K)
    order = jnp.argsort(key_lo, stable=True)
    order = order[jnp.argsort(key_hi[order], stable=True)]
    return order


# ---------------------------------------------------------------------------
# Build
# ---------------------------------------------------------------------------

def assemble_sorted_forest(proj_t: jax.Array, codes_t: jax.Array,
                           order: jax.Array, *, n: int,
                           leaf_size: int) -> dict:
    """Gather per-tree sorted layouts + leaf summaries for all L trees.

    proj_t/codes_t (L, n, K) in input row order, order (L, n) sorting
    permutations.  Returns the DEForest arrays (minus breakpoints/statics)
    in their storage dtypes (codes uint8, bounds int16).
    """
    with jax.named_scope("assemble"):
        L, _, K = proj_t.shape
        n_leaves = -(-n // leaf_size)
        n_pad = n_leaves * leaf_size
        pad = n_pad - n

        proj_s = jnp.take_along_axis(proj_t, order[..., None], axis=1)
        codes_s = jnp.take_along_axis(codes_t.astype(jnp.int32),
                                      order[..., None], axis=1)
        proj_s = jnp.pad(proj_s, ((0, 0), (0, pad), (0, 0)))
        codes_s = jnp.pad(codes_s, ((0, 0), (0, pad), (0, 0)))
        ids = jnp.pad(order.astype(jnp.int32), ((0, 0), (0, pad)),
                      constant_values=n)
        valid = jnp.broadcast_to(jnp.arange(n_pad) < n, (L, n_pad))

        blocks = codes_s.reshape(L, n_leaves, leaf_size, K)
        bmask = valid.reshape(L, n_leaves, leaf_size)
        big = jnp.iinfo(jnp.int32).max
        lo = jnp.where(bmask[..., None], blocks, big).min(axis=2)
        hi = jnp.where(bmask[..., None], blocks, -1).max(axis=2)
        leaf_valid = bmask.any(axis=2)
        lo = jnp.where(leaf_valid[..., None], lo, 0).astype(LEAF_DTYPE)
        hi = jnp.where(leaf_valid[..., None], hi, 0).astype(LEAF_DTYPE)

        return dict(point_ids=ids, proj_sorted=proj_s,
                    codes_sorted=codes_s.astype(CODE_DTYPE), valid=valid,
                    leaf_lo=lo, leaf_hi=hi, leaf_valid=leaf_valid)


def check_nr(Nr: int) -> None:
    """uint8 code storage: every builder entry point must refuse Nr > 256
    or codes would silently wrap mod 256."""
    if Nr > MAX_NR:
        raise ValueError(f"Nr={Nr} > {MAX_NR}: region ids are stored as "
                         f"uint8 symbols (paper's 8-bit alphabet)")


def fused_forest_arrays(proj_all: jax.Array, bp_all: jax.Array, *, K: int,
                        L: int, leaf_size: int, impl: str = "auto",
                        chunk: int = 512) -> dict:
    """Fused encode+key-pack -> single sort -> assemble, from (n, L*K)
    projections.  Trace-compatible (used inside the PDET shard_map build);
    ``impl`` picks the encode+pack kernel ('auto' = Pallas on TPU, the pure
    XLA oracle elsewhere), ``chunk`` its row-block size.
    """
    check_nr(bp_all.shape[1] - 1)
    n = proj_all.shape[0]
    if impl == "xla":
        from repro.kernels import ref as kref
        proj_t, codes_t, key_hi, key_lo = kref.encode_pack(
            proj_all, bp_all, K=K, L=L)
    else:
        from repro.kernels import ops as kops
        proj_t, codes_t, key_hi, key_lo = kops.encode_pack(
            proj_all, bp_all, K=K, L=L, block_n=chunk,
            interpret=(impl == "pallas_interpret"))
    order = code_sort_orders(key_hi, key_lo, K)
    return assemble_sorted_forest(proj_t, codes_t, order, n=n,
                                  leaf_size=leaf_size)


@functools.partial(jax.jit,
                   static_argnames=("K", "L", "leaf_size", "impl", "chunk"))
def _fused_build_jit(proj_all, bp_all, *, K, L, leaf_size, impl, chunk):
    return fused_forest_arrays(proj_all, bp_all, K=K, L=L,
                               leaf_size=leaf_size, impl=impl, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("K", "L", "impl", "chunk"))
def _encode_pack_jit(proj_all, bp_all, *, K, L, impl, chunk):
    if impl == "xla":
        from repro.kernels import ref as kref
        return kref.encode_pack(proj_all, bp_all, K=K, L=L)
    from repro.kernels import ops as kops
    return kops.encode_pack(proj_all, bp_all, K=K, L=L, block_n=chunk,
                            interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("n", "leaf_size"))
def _assemble_jit(proj_t, codes_t, order, *, n, leaf_size):
    return assemble_sorted_forest(proj_t, codes_t, order, n=n,
                                  leaf_size=leaf_size)


def _fused_build_arrays(proj_all, bp_all, *, K, L, leaf_size, impl,
                        chunk) -> dict:
    """Eager fused-build entry: on the CPU backend the key sort runs on
    the host (``code_sort_orders``' lexsort fast path) between the two
    jitted stages; elsewhere (and under an outer trace) everything fuses
    into the single jitted pipeline."""
    if (not isinstance(proj_all, jax.core.Tracer)
            and jax.default_backend() == "cpu"):
        proj_t, codes_t, key_hi, key_lo = _encode_pack_jit(
            proj_all, bp_all, K=K, L=L, impl=impl, chunk=chunk)
        order = code_sort_orders(key_hi, key_lo, K)
        return _assemble_jit(proj_t, codes_t, order,
                             n=proj_all.shape[0], leaf_size=leaf_size)
    return _fused_build_jit(proj_all, bp_all, K=K, L=L,
                            leaf_size=leaf_size, impl=impl, chunk=chunk)


def build_tree(proj: jax.Array, codes: jax.Array, breakpoints: jax.Array,
               leaf_size: int) -> dict:
    """Build one DE-Tree (array form) from (n, K) projections + codes.

    The seed per-tree path (double stable argsort), kept as the reference
    builder (``build_impl='reference'``), the oracle the fused pipeline is
    property-tested against, and the per-(batch, head) builder of
    ``det_attention``.
    """
    n, K = proj.shape
    order = _sort_by_code(codes, K)
    n_leaves = -(-n // leaf_size)
    n_pad = n_leaves * leaf_size
    pad = n_pad - n

    ids = jnp.pad(order.astype(jnp.int32), (0, pad), constant_values=n)
    valid = jnp.arange(n_pad) < n
    proj_s = jnp.pad(proj[order], ((0, pad), (0, 0)), constant_values=0.0)
    codes_s = jnp.pad(codes[order].astype(jnp.int32), ((0, pad), (0, 0)),
                      constant_values=0)

    blocks = codes_s.reshape(n_leaves, leaf_size, K)
    bmask = valid.reshape(n_leaves, leaf_size)
    big = jnp.iinfo(jnp.int32).max
    lo = jnp.where(bmask[..., None], blocks, big).min(axis=1)
    hi = jnp.where(bmask[..., None], blocks, -1).max(axis=1)
    leaf_valid = bmask.any(axis=1)
    lo = jnp.where(leaf_valid[:, None], lo, 0).astype(LEAF_DTYPE)
    hi = jnp.where(leaf_valid[:, None], hi, 0).astype(LEAF_DTYPE)

    return dict(point_ids=ids, proj_sorted=proj_s,
                codes_sorted=codes_s.astype(CODE_DTYPE),
                valid=valid, leaf_lo=lo, leaf_hi=hi, leaf_valid=leaf_valid,
                breakpoints=breakpoints)


def build_forest(proj_all: jax.Array, K: int, L: int, *,
                 Nr: int = enc.DEFAULT_NR, leaf_size: int = 64,
                 breakpoint_method: str = "sample_sort",
                 key: jax.Array | None = None,
                 encode_impl: str = "auto",
                 breakpoints: jax.Array | None = None,
                 build_impl: str = "auto",
                 build_chunk: int = 512) -> DEForest:
    """Build L DE-Trees from projections (n, L*K) (paper Alg. 1 + Alg. 2).

    ``breakpoints`` ((L*K, Nr+1), optional) bypasses breakpoint selection
    and encodes with the given *frozen* edges — the streaming index's seal
    path, which must encode new points into the base build's quantization so
    segment codes stay mutually comparable (docs/DESIGN.md §5).

    ``build_impl`` selects the builder: 'auto'/'xla'/'pallas'/
    'pallas_interpret' run the fused single-sort pipeline (one jitted call:
    encode+key-pack kernel, one stable sort for all L trees, vectorized
    gather + leaf summaries), with ``build_chunk`` as the kernel's row-block
    size; 'reference' runs the seed per-tree double-argsort path.  Both
    produce bit-identical forests (tests/test_build_fused.py).
    """
    n = proj_all.shape[0]
    assert proj_all.shape[1] == L * K, (proj_all.shape, L, K)
    check_nr(Nr)
    if breakpoints is None:
        bp_all = enc.select_breakpoints(proj_all, Nr,
                                        method=breakpoint_method,
                                        key=key)                   # (L*K, Nr+1)
    else:
        bp_all = breakpoints
        assert bp_all.shape == (L * K, Nr + 1), (bp_all.shape, L * K, Nr)
    bp_t = bp_all.reshape(L, K, Nr + 1)

    if build_impl == "reference":
        codes_all = enc.encode(proj_all, bp_all, impl=encode_impl)  # (n, L*K)
        proj_t = proj_all.reshape(n, L, K).transpose(1, 0, 2)       # (L, n, K)
        codes_t = codes_all.reshape(n, L, K).transpose(1, 0, 2)
        parts = jax.vmap(functools.partial(build_tree,
                                           leaf_size=leaf_size))(
            proj_t, codes_t, bp_t)
        return DEForest(n=n, leaf_size=leaf_size, **parts)

    impl = build_impl
    if impl == "auto" and encode_impl != "auto":
        impl = encode_impl            # an explicit encode impl wins on auto
    arrays = _fused_build_arrays(
        proj_all, bp_all, K=K, L=L, leaf_size=leaf_size, impl=impl,
        chunk=int(build_chunk) if build_chunk else 512)
    return DEForest(n=n, leaf_size=leaf_size, breakpoints=bp_t, **arrays)


# ---------------------------------------------------------------------------
# Leaf LB/UB bounds (paper Fig. 5)
# ---------------------------------------------------------------------------

def leaf_bounds(q_proj: jax.Array, leaf_lo: jax.Array, leaf_hi: jax.Array,
                leaf_valid: jax.Array, breakpoints: jax.Array, *,
                impl: str = "auto") -> tuple[jax.Array, jax.Array]:
    """LB/UB distances from a projected query to every leaf of one tree.

    q_proj: (K,); leaf_lo/hi: (n_leaves, K); breakpoints: (K, Nr+1).
    Returns (lb, ub), each (n_leaves,).  Invalid leaves get lb = ub = +inf.
    """
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.leaf_bounds(q_proj, leaf_lo, leaf_hi, leaf_valid,
                                breakpoints,
                                interpret=(impl == "pallas_interpret"))
    # Coordinates of the leaf's bounding box edges (int16 indices widen in
    # the gather).
    b_lo = _gather_edges(breakpoints, leaf_lo)                     # (n_leaves, K)
    b_hi = _gather_edges(breakpoints, leaf_hi.astype(jnp.int32) + 1)
    d_lo = b_lo - q_proj[None, :]
    d_hi = q_proj[None, :] - b_hi
    lb_dim = jnp.maximum(jnp.maximum(d_lo, d_hi), 0.0)
    ub_dim = jnp.maximum(jnp.abs(q_proj[None, :] - b_lo),
                         jnp.abs(q_proj[None, :] - b_hi))
    lb = jnp.sqrt(jnp.sum(lb_dim * lb_dim, axis=1))
    ub = jnp.sqrt(jnp.sum(ub_dim * ub_dim, axis=1))
    inf = jnp.inf
    lb = jnp.where(leaf_valid, lb, inf)
    ub = jnp.where(leaf_valid, ub, inf)
    return lb, ub


def _gather_edges(breakpoints: jax.Array, idx: jax.Array) -> jax.Array:
    """breakpoints (K, Nr+1), idx (n_leaves, K) -> coords (n_leaves, K)."""
    E = breakpoints.shape[1]
    idx = jnp.clip(idx.astype(jnp.int32), 0, E - 1)
    return jax.vmap(lambda bp_k, i_k: bp_k[i_k], in_axes=(0, 1), out_axes=1)(
        breakpoints, idx)
