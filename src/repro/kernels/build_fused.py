"""Pallas kernel: fused build pipeline — project -> encode -> key-pack.

The indexing phase of DET-LSH (the paper's headline speedup: "up to 6x for
DET-LSH, 40x for PDET-LSH over SOTA") was three separate HBM passes in the
seed build: the projection matmul, the encode compare-sweep, and a per-bit
Python loop packing interleaved sort keys — each materializing an (n, L*K)
intermediate plus its (L, n, K) transposed copy.  This kernel streams row
chunks of the input through all three stages in ONE grid pass:

  1. project: the (bn, d) row tile against the full (d, L*K) panel on the
     MXU (identical tiling to ``lsh_project``) — or skipped when the caller
     already has projections (the static build projects first because
     breakpoint *selection* needs the projected coordinates);
  2. encode: the compare-accumulate sweep over the Nr-1 internal breakpoint
     edges (identical formulation to ``encode_bins``), entirely on the VPU
     tile — region ids never round-trip through HBM before packing;
  3. key-pack: the MSB-first round-robin bit-interleave of each tree's K
     region ids into two uint32 words (the packed 64-bit sort key; see
     ``core.detree.interleave_keys``), unrolled over the static (level,
     dim) table and vectorized over the L trees.

Outputs keep n on the 128-lane axis: codes (and, when the kernel projects,
projections) as (K*L, n) rows and key words as (L, n).  An (L, n, K) output
would put K on the lanes and pad every row of it 128/K-fold in HBM.  The
ops wrapper hands the kernel its projection columns in *dim-major* order
(column j*L + l is dim j of tree l), so dim j of all L trees is one
contiguous (L, bn) slab of rows for the key pack, and transposes the small
(K*L, n) rows back into the per-tree (L, n, K) layout the sorted forest
consumes.

Grid: (n / block_n,) row chunks — ``block_n`` is the build's chunk size,
plumbed from ``IndexSpec.build_chunk``; it must be a multiple of 128.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.detree import key_bit_budget

# Projections define the hash; keep f32 products on the MXU so every path
# (XLA, kernel, query side) computes the same function.
PRECISION = jax.lax.Precision.HIGHEST


def _encode_pack_tile(proj, bp_ref, codes_ref, hi_ref, lo_ref, *,
                      K: int, L: int, Nr: int):
    """Shared tile body: proj (bn, Dp) f32 resident in VMEM, columns in
    dim-major order (column j*L + l is dim j of tree l; Dp = L*K padded to
    the lane width) -> codes (K*L, bn) and key words (L, bn)."""
    def body(b, acc):
        edges = bp_ref[pl.ds(b, 1), :]                 # (1, Dp) internal edge b
        return acc + jnp.where(proj >= edges, 1, 0)

    acc = jax.lax.fori_loop(1, Nr, body, jnp.zeros(proj.shape, jnp.int32))
    codes = jnp.clip(acc, 0, Nr - 1).T[:K * L]         # (K*L, bn)
    codes_ref[...] = codes

    _, hi_bits, lo_bits = key_bit_budget(K)
    dims = [codes[j * L:(j + 1) * L] for j in range(K)]   # (L, bn) each

    def pack(start_bit, nbits):
        key = jnp.zeros(dims[0].shape, jnp.uint32)
        pos = nbits * K
        for b in range(nbits):                         # bit level (MSB first)
            for j in range(K):                         # round-robin over dims
                pos -= 1
                if pos >= 32:      # overflows the word: dropped, explicitly
                    continue       # (mirrors detree.interleave_keys)
                bit = (dims[j] >> (7 - (start_bit + b))) & 1
                key = key | (bit.astype(jnp.uint32) << pos)
        return key

    hi_ref[...] = pack(0, hi_bits)
    lo_ref[...] = (pack(hi_bits, lo_bits) if lo_bits > 0
                   else jnp.zeros(hi_ref.shape, jnp.uint32))


def _kernel_from_proj(p_ref, bp_ref, codes_ref, hi_ref, lo_ref, *, K, L, Nr):
    _encode_pack_tile(p_ref[...], bp_ref, codes_ref, hi_ref, lo_ref,
                      K=K, L=L, Nr=Nr)


def _kernel_from_data(x_ref, a_ref, bp_ref, proj_ref, codes_ref, hi_ref,
                      lo_ref, *, K, L, Nr):
    proj = jax.lax.dot_general(x_ref[...], a_ref[...],
                               (((1,), (0,)), ((), ())),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)
    proj_ref[...] = proj.T[:K * L]                     # (K*L, bn)
    _encode_pack_tile(proj, bp_ref, codes_ref, hi_ref, lo_ref,
                      K=K, L=L, Nr=Nr)


def _out_specs(n: int, K: int, L: int, block_n: int):
    rows = pl.BlockSpec((K * L, block_n), lambda i: (0, i))
    keys = pl.BlockSpec((L, block_n), lambda i: (0, i))
    specs = [rows, keys, keys]
    shapes = [jax.ShapeDtypeStruct((K * L, n), jnp.int32),
              jax.ShapeDtypeStruct((L, n), jnp.uint32),
              jax.ShapeDtypeStruct((L, n), jnp.uint32)]
    return specs, shapes


def encode_pack(proj: jax.Array, breakpoints_t: jax.Array, *, K: int,
                L: int, block_n: int = 512, interpret: bool = False):
    """proj (n, Dp), breakpoints_t (Nr+1, Dp) — the first L*K columns are
    real and dim-major, the rest lane padding — -> (codes (K*L, n) i32,
    key_hi (L, n) u32, key_lo (L, n) u32).  n must be a block_n multiple
    (ops.py pads and orders the columns)."""
    n, Dp = proj.shape
    E = breakpoints_t.shape[0]
    assert Dp >= L * K and breakpoints_t.shape[1] == Dp, (proj.shape, L, K)
    assert n % block_n == 0, (n, block_n)
    out_specs, out_shape = _out_specs(n, K, L, block_n)
    return pl.pallas_call(
        lambda *refs: _kernel_from_proj(*refs, K=K, L=L, Nr=E - 1),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, Dp), lambda i: (i, 0)),
            pl.BlockSpec((E, Dp), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(proj, breakpoints_t)


def project_encode_pack(x: jax.Array, a: jax.Array, breakpoints_t: jax.Array,
                        *, K: int, L: int, block_n: int = 256,
                        interpret: bool = False):
    """x (n, d), a (d, Dp), breakpoints_t (Nr+1, Dp) -> (proj (K*L, n) f32,
    codes, key_hi, key_lo) as :func:`encode_pack`, with the projection
    matmul fused into the pass (the streaming seal / frozen-breakpoint
    path, where no breakpoint selection sits between projection and
    encoding).  n, d and Dp must be block-aligned (ops.py pads rows to
    block_n and d, Dp to the 128-lane MXU width)."""
    n, d = x.shape
    Dp = a.shape[1]
    E = breakpoints_t.shape[0]
    assert Dp >= L * K and breakpoints_t.shape[1] == Dp, (a.shape, L, K)
    assert n % block_n == 0, (n, block_n)
    out_specs, out_shape = _out_specs(n, K, L, block_n)
    return pl.pallas_call(
        lambda *refs: _kernel_from_data(*refs, K=K, L=L, Nr=E - 1),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, Dp), lambda i: (0, 0)),
            pl.BlockSpec((E, Dp), lambda i: (0, 0)),
        ],
        out_specs=[out_specs[0]] + out_specs,
        out_shape=[jax.ShapeDtypeStruct((K * L, n), jnp.float32)] + out_shape,
        interpret=interpret,
    )(x, a, breakpoints_t)
