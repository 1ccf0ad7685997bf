"""Pallas kernel: p-stable LSH projection — X(n,d) @ A(d, K*L).

The hashing phase of DET-LSH (paper: "computing hash values for n points",
O(L*K*n*d), the dominant indexing FLOPs).  A tall-skinny matmul: n is large,
m = K*L is small (typically 64).  Tiling: grid over row blocks of X; each
program loads an (bn, d) X tile and the full (d, m) A panel into VMEM and
issues one MXU matmul.  m and d are padded to the 128-lane boundary by the
ops.py wrapper so every matmul dimension is hardware-aligned.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, a_ref, o_ref):
    x = x_ref[...]
    a = a_ref[...]
    o_ref[...] = jax.lax.dot_general(
        x, a, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def lsh_project(x: jax.Array, a: jax.Array, *, block_n: int = 256,
                interpret: bool = False) -> jax.Array:
    """x (n, d), a (d, m) -> (n, m) f32.  n, d, m must be block-aligned
    (the ops.py wrapper pads)."""
    n, d = x.shape
    m = a.shape[1]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((d, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, m), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(x, a)
