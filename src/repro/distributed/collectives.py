"""Collective building blocks for the model's distributed paths.

The centerpiece is sequence-parallel decode attention: for decode shapes the
KV cache is sharded along the *sequence* axis (decode_32k: over "model";
long_500k: over "data" and "model" — batch=1 leaves both axes free), each
shard runs the local flash-decode kernel over its cache slice, and the
partial (o, m, l) softmax stats are combined with one tiny all-reduce —
FlashDecoding's split-K reduction mapped onto mesh axes.

This is exactly a Task Bench `all_to_all`-class dependence carried by a
psum-sized message (stats + per-head output), i.e. the communication term it
adds to the roofline is O(B x Hq x D) per layer, independent of cache length.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.kernels import ops

AxisRef = Union[str, Tuple[str, ...]]


def _axes_tuple(ref: AxisRef) -> Tuple[str, ...]:
    return (ref,) if isinstance(ref, str) else tuple(ref)


def sequence_parallel_decode_attention(
    q: jax.Array,        # (B, Hq, D) — replicated over the seq axes
    k_cache: jax.Array,  # (B, Hkv, S, D) — S sharded over `seq_axes`
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) global valid length
    *,
    mesh: Mesh,
    seq_axes: AxisRef,
    batch_axis: Optional[AxisRef] = None,
    window: int = 0,
    sm_scale: Optional[float] = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Distributed flash-decode with lse-combine across `seq_axes`."""
    seq_axes = _axes_tuple(seq_axes)
    batch_axes = _axes_tuple(batch_axis) if batch_axis else ()
    n_seq_shards = 1
    for a in seq_axes:
        n_seq_shards *= mesh.shape[a]
    S = k_cache.shape[2]
    if S % n_seq_shards:
        raise ValueError(f"cache length {S} not divisible by {n_seq_shards}")
    S_local = S // n_seq_shards

    bspec = batch_axes[0] if len(batch_axes) == 1 else (batch_axes or None)
    sspec = seq_axes[0] if len(seq_axes) == 1 else seq_axes
    cache_spec = P(bspec, None, sspec, None)
    q_spec = P(bspec, None, None)
    len_spec = P(bspec)

    def local(qx, kc, vc, ln):
        # global offset of this shard's cache slice
        idx = 0
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        offset = idx * S_local
        # Lengths in local coordinates, deliberately UNclipped: lengths' =
        # ln - offset. Validity pos < lengths' and (window) pos >= lengths' -
        # window both shift correctly; lengths' <= 0 masks the whole shard
        # (l = 0, handled by the combine), lengths' > S_local keeps it fully
        # visible — both are exactly right globally.
        local_len = (ln - offset).astype(jnp.int32)
        o, m, l = ops.decode_attention(
            qx, kc, vc, local_len,
            window=window, sm_scale=sm_scale, return_stats=True,
            use_kernel=use_kernel,
        )
        # cross-shard lse combine over the sequence axes
        m_g = jax.lax.pmax(m, seq_axes)  # (B, Hq)
        scale = l * jnp.exp(m - m_g)
        num = jax.lax.psum(o.astype(jnp.float32) * scale[..., None], seq_axes)
        den = jax.lax.psum(scale, seq_axes)
        den = jnp.where(den == 0.0, 1.0, den)
        # psum output is invariant over seq_axes, matching the replicated
        # out_spec (every shard returns the same combined attention output).
        return (num / den[..., None]).astype(qx.dtype)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, len_spec),
        out_specs=q_spec,
        # pallas_call inside shard_map cannot declare vma on its out_shape
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, lengths)


def hierarchical_psum_spec(axes: Sequence[str]) -> Tuple[str, ...]:
    """Gradient-reduction axis order: innermost (fast ICI) axis first so the
    inter-pod (DCI) hop carries the already-reduced tensor once."""
    return tuple(axes)
