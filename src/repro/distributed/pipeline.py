"""GPipe-style pipeline parallelism over a mesh axis (the ``pod`` axis of
the multi-pod mesh) via shard_map + collective_permute.

At 1000+-node scale, FSDP all-gathers across pods ride the slow inter-pod
links; placing pipeline *stages* on pods instead bounds every FSDP/TP
collective to a single pod and moves only microbatch activations across
pods (P2P ppermute) — the standard large-cluster composition
(PP-over-pods × FSDP×TP-within-pod).

The schedule below is the classic GPipe fill-drain loop: with S stages and
M microbatches, each device runs ``S + M - 1`` ticks; device s computes
microbatch (t - s) when 0 ≤ t - s < M, and activations hop s → s+1 between
ticks.  Bubble fraction = (S-1)/(S+M-1).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, mesh: Mesh,
                   axis: str = "pod") -> jnp.ndarray:
    """Run ``stage_fn`` as a pipeline over ``axis``.

    stage_params: pytree whose leaves have leading dim = #stages (sharded
      over ``axis`` — each device holds its own stage's slice).
    x: [M, mb, ...] microbatched input (M = #microbatches, replicated over
      ``axis``; other mesh axes may shard the trailing dims as usual).
    Returns [M, mb, ...] outputs.
    """
    S = mesh.shape[axis]
    M = x.shape[0]
    n_ticks = S + M - 1

    def per_stage(params_slice, xs):
        # params_slice: this device's stage params (leading dim 1)
        params_local = jax.tree_util.tree_map(lambda l: l[0], params_slice)
        s = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)      # activation register
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t from xs; others use the buffer
            mb_idx = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(s == 0,
                             jax.lax.dynamic_index_in_dim(
                                 xs, mb_idx, keepdims=False),
                             buf)
            active = (t - s >= 0) & (t - s < M)
            y = stage_fn(params_local, x_in)
            y = jnp.where(active, y, buf)
            # last stage banks its result at slot (t - s)
            out_idx = jnp.clip(t - s, 0, M - 1)
            outs = jnp.where(
                active & (s == S - 1),
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, out_idx, axis=0),
                outs)
            # hop activations s -> s+1
            perm = [(i, i + 1) for i in range(S - 1)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs)

        buf, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # results live on the last stage; broadcast them to every stage so
        # the out_spec can be replicated over the pipeline axis
        outs = jax.lax.psum(
            jnp.where(s == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    other = tuple(a for a in mesh.axis_names if a != axis)
    xspec = P(*((None,) * x.ndim))
    return jax.shard_map(
        per_stage, mesh=mesh, in_specs=(pspec, xspec),
        out_specs=xspec, check_vma=False,
    )(stage_params, x)
