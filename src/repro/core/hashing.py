"""p-stable LSH projections (paper §II-B, Eq. 1).

h(o) = a . o with a ~ N(0, I_d).  DET-LSH uses K*L such functions, giving L
independent K-dimensional projected spaces:  H_i(o) in R^K, i = 1..L.

The projection is a tall-skinny matmul — the hashing hot spot.  The Pallas
kernel lives in ``repro.kernels.lsh_project``; this module provides the
weight sampling and the jnp fallback used on CPU / in dry-runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_projections(key: jax.Array, d: int, K: int, L: int,
                       dtype=jnp.float32) -> jax.Array:
    """Sample the (d, L*K) projection matrix A with i.i.d. N(0,1) entries."""
    return jax.random.normal(key, (d, L * K), dtype=dtype)


def project(data: jax.Array, A: jax.Array, *, impl: str = "auto") -> jax.Array:
    """Project ``data`` (n, d) -> (n, L*K) with the p-stable family.

    impl: 'auto' | 'xla' | 'pallas' | 'pallas_interpret'.
    """
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops
        return kops.lsh_project(data, A,
                                interpret=(impl == "pallas_interpret"))
    # XLA path (used by dry-run lowering and CPU execution).
    return project_query(data, A)


def project_query(q: jax.Array, A: jax.Array) -> jax.Array:
    """Project one query or a batch of queries: (..., d) -> (..., L*K).

    f32 products on every backend (the TPU default would round the
    operands to bf16): data and queries must hash through the same
    function for the leaf bounds to stay admissible."""
    return jnp.dot(q, A, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
