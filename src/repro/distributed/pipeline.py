"""Pipeline parallelism over a mesh axis (collective-permute schedule).

GPipe-style microbatch pipeline expressed in shard_map: stage s holds the
stacked params slice for its layers; activations flow stage->stage+1 via
ppermute once per tick. With M microbatches and S stages the schedule runs
M + S - 1 ticks; each device computes on M of them (utilization M/(M+S-1) —
overdecomposition again: more microbatches per stage hide the bubble, the
paper's §6.2 story in pipeline form).

The assigned production meshes use DP x TP, so PP is an optional axis here:
it is exercised by tests (equivalence vs sequential apply, on an
8-device virtual mesh). The same ppermute schedule is what a
`dom`-pattern Task Bench graph measures (DESIGN.md §3).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P



def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves stacked (n_stages, ...) and sharded over axis
    x: jax.Array,  # (M, mb, ...) microbatched input
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """Run x through n_stages sequential stages, pipelined over `axis`."""
    S = mesh.shape[axis]
    M = x.shape[0]
    ticks = M + S - 1
    fwd = [(d, (d + 1) % S) for d in range(S)]

    def local(params_local, xs_local):
        # params_local: this stage's params (leading stacked dim of size 1)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        # pad the microbatch stream to the tick count
        pad = jnp.zeros((ticks - M,) + xs_local.shape[1:], xs_local.dtype)
        stream = jnp.concatenate([xs_local, pad], axis=0)

        def tick(carry, t):
            recv, outs = carry
            inject = jax.lax.dynamic_index_in_dim(stream, jnp.minimum(t, M - 1),
                                                  0, keepdims=False)
            inp = jnp.where(stage == 0, inject, recv)
            out = stage_fn(params_local, inp)
            nxt = jax.lax.ppermute(out, axis, fwd)
            # last stage banks its result for microbatch m = t - (S - 1)
            m = t - (S - 1)
            outs = jax.lax.cond(
                (stage == S - 1) & (m >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(m, 0), 0),
                lambda o: o,
                outs,
            )
            return (nxt, outs), None

        recv0 = jnp.zeros_like(stage_fn(params_local, stream[0]))
        outs0 = jnp.zeros((M,) + recv0.shape, recv0.dtype)
        (_, outs), _ = jax.lax.scan(tick, (recv0, outs0), jnp.arange(ticks))
        # broadcast final outputs from the last stage to all stages (masked
        # psum — ppermute cannot fan out) so out_specs can be replicated
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis
        )
        return outs

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),  # params stage-sharded; stream replicated
        out_specs=P(),
        check_vma=False,  # ppermute fan-out breaks the static VMA analysis
    )
    return fn(stage_params, x)
