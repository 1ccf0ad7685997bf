"""Base vectors and queries of a configuration, made on the device from a seed.

The shape of the data set (cluster centres and per-cluster spreads) comes
from the configuration's fixed ``data_seed``: it is part of the deployment,
as the published data set is.  The run's ``--seed`` draws which cluster each
point belongs to, its noise, and the held-out queries, so every seed sees a
fresh sample of one distribution and does the same amount of work.

Generators, by the configuration's ``data.generator``:

  sift_like  non-negative clustered vectors (SIFT descriptors are
             histograms of gradients: non-negative, many near-zero bins);
  deep_like  clustered vectors with every row L2-normalised (Deep1B rows
             are normalised CNN descriptors).

Queries are held-out draws from the same generator, never perturbed base
rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed.  ``jax.random.key`` keeps only
    the low 32 bits of larger seeds, so the high bits are folded in."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _shape(gen: dict, d: int, key: jax.Array):
    kc, km, ks = jax.random.split(key, 3)
    n_clusters = gen["clusters"]
    if gen["generator"] == "sift_like":
        on = jax.random.uniform(km, (n_clusters, d)) < gen["density"]
        centres = gen["centre_scale"] * jnp.abs(
            jax.random.normal(kc, (n_clusters, d), jnp.float32)) * on
    else:
        centres = jax.random.normal(kc, (n_clusters, d), jnp.float32)
    lo, hi = gen["spread"]
    spreads = jax.random.uniform(ks, (n_clusters, 1), jnp.float32, lo, hi)
    return centres, spreads


def _draw(gen: dict, centres, spreads, key: jax.Array, m: int):
    ka, kn = jax.random.split(key)
    d = centres.shape[1]
    assign = jax.random.randint(ka, (m,), 0, centres.shape[0])
    x = centres[assign] + spreads[assign] * jax.random.normal(
        kn, (m, d), jnp.float32)
    if gen["generator"] == "sift_like":
        return jnp.maximum(x, 0.0)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n", "n_queries", "d", "gen"))
def _make(shape_key, seed_key_, *, n, n_queries, d, gen):
    gen = dict(gen)
    centres, spreads = _shape(gen, d, shape_key)
    kb, kq = jax.random.split(seed_key_)
    return (_draw(gen, centres, spreads, kb, n),
            _draw(gen, centres, spreads, kq, n_queries))


def make(cfg: dict, seed: int):
    """(base (n, d), queries (n_queries, d)) f32 on the default device, in
    one jitted call."""
    gen = cfg["data"]
    frozen = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in gen.items() if k != "data_seed"))
    return _make(seed_key(gen["data_seed"]), seed_key(seed), n=cfg["n"],
                 n_queries=cfg["n_queries"], d=cfg["d"], gen=frozen)
