"""Pallas kernel: exact-distance rerank (the paper's SIMD distance hot spot).

Computes all pairwise Euclidean distances between a query block and a
candidate block — the fine-grained verification step of the two-step query
strategy ("compute the real distance of each candidate point", O(beta*n*d)).

Tiling: grid (b/bq, m/bc); each program holds a (bq, d) query tile and a
(bc, d) candidate tile in VMEM, computes the cross term on the MXU
(dot(q, c^T)) and fuses the norm terms and sqrt on the VPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, c_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)                  # (bq, d)
    c = c_ref[...].astype(jnp.float32)                  # (bc, d)
    qq = jnp.sum(q * q, axis=1, keepdims=True)          # (bq, 1)
    cc = jnp.sum(c * c, axis=1)[None, :]                # (1, bc)
    qc = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    o_ref[...] = jnp.sqrt(jnp.maximum(qq - 2.0 * qc + cc, 0.0))


def l2_rerank(q: jax.Array, c: jax.Array, *, block_q: int = 128,
              block_c: int = 256, interpret: bool = False) -> jax.Array:
    """q (b, d), c (m, d) -> distances (b, m) f32 (block-aligned; ops pads)."""
    b, d = q.shape
    m = c.shape[0]
    assert b % block_q == 0 and m % block_c == 0, (b, m, block_q, block_c)
    grid = (b // block_q, m // block_c)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        interpret=interpret,
    )(q, c)
