"""GPipe-style pipeline parallelism over a mesh axis (feature demo).

Layers are split into S stages sharded over a mesh axis (e.g. the `pod`
axis); microbatches stream through with the classic (M + S - 1)-tick
schedule; activations hop stages via ``ppermute`` (autodiff
transposes the permute, so ``jax.grad`` through the pipelined forward gives
1F1B-equivalent gradients without extra machinery).

This is deliberately compact: the production configs default to
FSDP+TP+EP+SP (see DESIGN.md §5) and pipelining is exercised by
``tests/test_pipeline.py`` at a 4-stage mesh as the PP capability proof.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_apply(stage_params, x_micro, stage_fn, mesh: Mesh,
                   axis: str = "stage"):
    """Run microbatches through S pipeline stages sharded over ``axis``.

    stage_params: pytree with leading dim S (sharded over ``axis``).
    x_micro: (M, micro_batch, ...) microbatched inputs (replicated).
    stage_fn(params_slice, x) -> y, applied by each stage.
    Returns (M, micro_batch, ...) outputs of the final stage.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    ticks = M + S - 1

    def inner(params_local, xs):
        # params_local: (1, ...) this stage's slice; xs: (M, mb, ...) full
        pslice = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            feed = jnp.where(t < M, t, M - 1)
            x_in = jnp.where((sid == 0) & (t < M), xs[feed], buf)
            active = (t >= sid) & (t - sid < M)
            y = stage_fn(pslice, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # pass activations downstream (stage i -> i+1)
            perm = [(i, (i + 1) % S) for i in range(S)]
            nxt = jax.lax.ppermute(y, axis, perm)
            # last stage records its finished microbatch
            done_idx = t - (S - 1)
            outs = jax.lax.cond(
                (sid == S - 1) & (done_idx >= 0),
                lambda o: o.at[jnp.maximum(done_idx, 0)].set(y),
                lambda o: o, outs)
            return (nxt, outs), None

        buf0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros((M,) + mb_shape, xs.dtype)
        (buf, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                      jnp.arange(ticks))
        # broadcast final outputs from the last stage to all ranks
        outs = jax.lax.psum(
            jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(), check_vma=False,
    )(stage_params, x_micro)


def sequential_reference(stage_params, x_micro, stage_fn):
    """Same computation without pipelining (oracle for tests)."""
    S = jax.tree.leaves(stage_params)[0].shape[0]

    def one_micro(x):
        for s in range(S):
            pslice = jax.tree.map(lambda a: a[s], stage_params)
            x = stage_fn(pslice, x)
        return x

    return jax.vmap(one_micro)(x_micro)
