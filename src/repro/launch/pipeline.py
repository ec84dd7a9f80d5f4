"""GPipe-style microbatch pipeline parallelism over a mesh axis.

For the multi-pod topology the natural PP mapping is stages over the
``pod`` axis (layers split across pods, activations ppermute over the
inter-pod links once per microbatch — bytes = microbatch activations,
far below the FSDP-style alternatives for cross-pod traffic).

Implementation: shard_map over the pipe axis; each rank holds its stage's
parameters; a fori_loop runs the (n_micro + n_stages - 1)-tick schedule,
ppermuting activations downstream each tick; the last stage scatters its
finished microbatch into the output buffer (psum'd at the end since only
one rank writes each slot).

Demonstrated + verified vs sequential execution in
tests/test_distributed.py (8 fake devices).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh, axis: str,
                   stage_params, x_micro: jnp.ndarray) -> jnp.ndarray:
    """Run ``y = stage_{S-1}(...stage_0(x))`` as a microbatch pipeline.

    stage_fn(params_slice, x) -> x'   (same shape, one pipeline stage)
    stage_params: pytree with leading dim = n_stages (sharded over axis)
    x_micro: [n_micro, mb, ...] microbatched input (replicated)
    Returns [n_micro, mb, ...] outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params_local, xm):
        # params_local leaves: [1, ...] (this rank's stage)
        rank = jax.lax.axis_index(axis)
        pl = jax.tree.map(lambda a: a[0], params_local)
        act = jnp.zeros_like(xm[0])
        out = jnp.zeros_like(xm)

        def tick(t, carry):
            act, out = carry
            # stage 0 ingests microbatch t (if any remain)
            inject = jnp.clip(t, 0, n_micro - 1)
            act = jnp.where(rank == 0,
                            jax.lax.dynamic_index_in_dim(
                                xm, inject, 0, keepdims=False), act)
            mb_idx = t - rank              # microbatch this rank holds
            valid = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
            y = stage_fn(pl, act)
            y = jnp.where(valid, y, act)
            # the last stage retires its finished microbatch
            done = jnp.logical_and(rank == n_stages - 1, valid)
            slot = jnp.clip(mb_idx, 0, n_micro - 1)
            upd = jnp.where(done, y, jax.lax.dynamic_index_in_dim(
                out, slot, 0, keepdims=False))
            out = jax.lax.dynamic_update_index_in_dim(out, upd, slot, 0)
            # shift activations downstream
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            act = jax.lax.ppermute(y, axis, perm)
            return act, out

        _, out = jax.lax.fori_loop(0, ticks, tick, (act, out))
        # only the last rank has real outputs; psum replicates them
        out = jnp.where(rank == n_stages - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(out, axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)
