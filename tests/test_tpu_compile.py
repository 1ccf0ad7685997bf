"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is needed: ``jax.experimental.topologies`` describes a v5e:2x2
host and the installed TPU compiler (Mosaic + XLA:TPU) compiles for it, so
every refusal interpret mode cannot see — block shapes off the (8, 128)
tiling, lane-dynamic indexing, i1 vector casts, VMEM overflow, a program
that does not fit 16 GiB of HBM — fails here, on the CPU.

``ops`` dispatches on ``jax.default_backend()``, which stays 'cpu'; each
test forces the compiled-Pallas branch with ``monkeypatch`` and checks
that a ``tpu_custom_call`` is in the compiled program.  The topology is
described inside a module fixture (never at import), so every xdist worker
collects the same tests and only the worker running this file loads the
TPU library.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas(monkeypatch):
    """Route the ops wrappers to the compiled kernels (as on a TPU) and
    drop traces cached by CPU-path calls of the same shapes."""
    monkeypatch.setattr(ops, "_use_pallas", lambda interpret: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _range_rerank_shapes(sds, *, L, B, K, nl, ls, d, E):
    return (sds((B, d), jnp.float32), sds((L, B, K), jnp.float32),
            sds((B,), jnp.float32), sds((L, nl, K), jnp.int16),
            sds((L, nl, K), jnp.int16), sds((L, nl), jnp.bool_),
            sds((L, K, E), jnp.float32), sds((L, nl * ls, d), jnp.float32),
            sds((L, nl * ls), jnp.bool_), sds((L, nl * ls), jnp.bool_))


@pytest.mark.parametrize("d", [96, 128, 960])
def test_range_rerank_compiles(one_chip, pallas, d):
    """The fused query kernel at real widths (Deep/SIFT/GIST), with a
    ragged last leaf block (1003 leaves, block_l=8)."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    shapes = _range_rerank_shapes(sds, L=8, B=64, K=4, nl=1003, ls=64, d=d,
                                  E=257)
    _compile(functools.partial(ops.range_rerank, leaf_size=64), *shapes)


def test_range_rerank_heads_compiles(one_chip, pallas):
    """The KV-decode entry: vmap over H forests lifts into the grid."""
    H = 4

    def sds(shape, dt):
        return jax.ShapeDtypeStruct((H,) + shape, dt, sharding=one_chip)
    shapes = list(_range_rerank_shapes(sds, L=4, B=8, K=4, nl=40, ls=32,
                                       d=64, E=17))
    shapes[2] = sds((8,), jnp.float32)
    _compile(functools.partial(ops.range_rerank_heads, leaf_size=32),
             *shapes)


@pytest.mark.parametrize("K,L", [(4, 8), (16, 4)])
def test_encode_pack_compiles(one_chip, pallas, K, L):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(functools.partial(ops.encode_pack, K=K, L=L, block_n=512),
             sds((100_000, L * K), jnp.float32), sds((L * K, 257),
                                                     jnp.float32))


@pytest.mark.parametrize("d", [96, 128, 960])
def test_project_encode_pack_compiles(one_chip, pallas, d):
    """The streaming seal kernel (projection fused) at a delta's size."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(functools.partial(ops.project_encode_pack, K=4, L=8,
                               block_n=512),
             sds((512, d), jnp.float32), sds((d, 32), jnp.float32),
             sds((32, 257), jnp.float32))


def test_fused_build_fits_hbm_at_sift1m(one_chip, pallas):
    """The whole jitted fused build (encode+pack kernel, key sort, gather,
    leaf summaries) at n = 1,000,000, L=8, K=4 fits one chip's HBM."""
    from repro.core import detree

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    n, K, L = 1_000_000, 4, 8
    build = functools.partial(detree._fused_build_jit, K=K, L=L,
                              leaf_size=64, impl="auto", chunk=512)
    mem = _compile(build, sds((n, L * K), jnp.float32),
                   sds((L * K, 257), jnp.float32)).memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"build needs {total / 2 ** 30:.2f} GiB"
