"""Public jit'd wrappers over the Pallas kernels.

Every op auto-selects interpret mode off-TPU (this container is CPU-only, so
kernels execute their Python bodies for validation; on a real TPU the same
call sites lower to Mosaic). ``use_kernel=False`` falls back to the jnp
reference — the training path uses references (differentiable), inference
paths use kernels.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bodies import memory_bound_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_chunk_pallas
from repro.kernels.taskbench_compute import taskbench_compute_pallas
from repro.kernels.taskbench_step import (
    taskbench_step_boundary,
    taskbench_step_carry,
    taskbench_step_interior,
    taskbench_step_pallas,
)


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def taskbench_compute(x: jax.Array, iterations: int) -> jax.Array:
    """Iterated-FMA task body; accepts (..., payload)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = taskbench_compute_pallas(x2, iterations, interpret=_interpret())
    return out.reshape(shape)


def taskbench_memory(x: jax.Array, iterations: int, scratch: int) -> jax.Array:
    """Scratch-sweep (memory-bound) task body; accepts (..., payload)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = memory_bound_pallas(x2, iterations, scratch, interpret=_interpret())
    return out.reshape(shape)


def taskbench_step(
    src: jax.Array, idx: jax.Array, wgt: jax.Array, act=None, **kw
) -> jax.Array:
    """Fused Task Bench timestep(s) (gather + combine + body) for K graphs.

    See repro.kernels.taskbench_step for the operand contract — including
    the temporal-blocked ``steps_per_launch`` path, which requires the
    (K, S) ``act`` depth mask; this wrapper only auto-selects interpret
    mode off-TPU.
    """
    return taskbench_step_pallas(src, idx, wgt, act,
                                 interpret=_interpret(), **kw)


def taskbench_carry(carry, wgt, *, offset: int, payload: int, **kw):
    """One S=1 window step on a tiled, halo-extended carry.
    See kernels.taskbench_step.taskbench_step_carry."""
    return taskbench_step_carry(carry, wgt, offset=offset, payload=payload,
                                interpret=_interpret(), **kw)


def taskbench_interior(src, idx, wgt, act, *, depth: int, **kw):
    """Interior phase of a pipelined blocked launch (owned block only;
    returns the (K, B - 2*depth, payload) rows valid after S shrinks).
    See kernels.taskbench_step.taskbench_step_interior."""
    return taskbench_step_interior(src, idx, wgt, act, depth=depth,
                                   interpret=_interpret(), **kw)


def taskbench_boundary(left, right, idx, wgt, act, *, depth: int, **kw):
    """Boundary phase of a pipelined blocked launch (both 3*depth edge
    buffers of all K members in ONE launch; returns the new edge rows).
    See kernels.taskbench_step.taskbench_step_boundary."""
    return taskbench_step_boundary(left, right, idx, wgt, act, depth=depth,
                                   interpret=_interpret(), **kw)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            use_kernel: bool = True) -> jax.Array:
    if not use_kernel:
        return ref.rmsnorm_ref(x, w, eps)
    shape = x.shape
    out = rmsnorm_pallas(x.reshape(-1, shape[-1]), w, eps=eps,
                         interpret=_interpret())
    return out.reshape(shape)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int = 0, sm_scale: Optional[float] = None,
    use_kernel: bool = True,
) -> jax.Array:
    if not use_kernel:
        # differentiable paths: dense oracle for short sequences, chunked
        # flash (scan + online softmax + remat) beyond — O(S) memory and a
        # realistic HLO cost shape for dry-run compiles (ref.py docstring)
        if k.shape[2] <= 2048:
            return ref.attention_ref(q, k, v, causal=causal, window=window,
                                     sm_scale=sm_scale)
        return ref.chunked_attention_ref(q, k, v, causal=causal,
                                         window=window, sm_scale=sm_scale)
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, sm_scale=sm_scale,
        interpret=_interpret(),
    )


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array, lengths: jax.Array,
    *, sm_scale: Optional[float] = None, window: int = 0,
    return_stats: bool = False, use_kernel: bool = True,
):
    """Returns o (B,Hq,D), or (o, m, l) softmax stats with return_stats=True
    (stats feed the cross-shard lse-combine in sequence-parallel decode)."""
    if not use_kernel:
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths,
                                        sm_scale=sm_scale, window=window,
                                        return_stats=return_stats)
    o, m, l = decode_attention_pallas(q, k_cache, v_cache, lengths,
                                      sm_scale=sm_scale, window=window,
                                      interpret=_interpret())
    if return_stats:
        return o, m, l
    return o


def ssd_chunk(
    x: jax.Array, b: jax.Array, c: jax.Array, dta: jax.Array, dt: jax.Array,
    *, use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    if not use_kernel:
        return ref.ssd_chunk_ref(x, b, c, dta, dt)
    return ssd_chunk_pallas(x, b, c, dta, dt, interpret=_interpret())


def ssd(
    x: jax.Array,    # (B, S, H, P)
    b: jax.Array,    # (B, S, G, N)
    c: jax.Array,    # (B, S, G, N)
    dta: jax.Array,  # (B, S, H)   dt * A (negative)
    dt: jax.Array,   # (B, S, H)
    *,
    chunk: int = 128,
    init_state: Optional[jax.Array] = None,  # (B, H, N, P)
    use_kernel: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence SSD: chunked intra-kernel + inter-chunk lax.scan.

    Returns (y: (B,S,H,P), final_state: (B,H,N,P)). Sequence length must be a
    multiple of ``chunk`` (callers pad); equivalence with the sequential
    recurrence is asserted in tests against ref.ssd_sequential_ref.
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    if S % chunk:
        raise ValueError(f"seq {S} not a multiple of chunk {chunk}")
    NC, T = S // chunk, chunk
    ratio = H // G

    # --- reshape into chunks, head-major for the kernel --------------------
    xc = x.reshape(B, NC, T, H, P).transpose(0, 1, 3, 2, 4).reshape(B * NC, H, T, P)
    bc = b.reshape(B, NC, T, G, N).transpose(0, 1, 3, 2, 4).reshape(B * NC, G, T, N)
    cc = c.reshape(B, NC, T, G, N).transpose(0, 1, 3, 2, 4).reshape(B * NC, G, T, N)
    dtac = dta.reshape(B, NC, T, H).transpose(0, 1, 3, 2).reshape(B * NC, H, T)
    dtc = dt.reshape(B, NC, T, H).transpose(0, 1, 3, 2).reshape(B * NC, H, T)

    y_intra, states = ssd_chunk(xc, bc, cc, dtac, dtc, use_kernel=use_kernel)
    y_intra = y_intra.reshape(B, NC, H, T, P)
    states = states.reshape(B, NC, H, N, P)

    # --- inter-chunk recurrence over the NC per-chunk states ---------------
    a_cum = jnp.cumsum(dtac.astype(jnp.float32), axis=-1).reshape(B, NC, H, T)
    chunk_decay = jnp.exp(a_cum[..., -1])  # (B, NC, H)
    ch = jnp.repeat(
        cc.reshape(B, NC, G, T, N), ratio, axis=2
    ).astype(jnp.float32)  # (B, NC, H, T, N)
    decay_in = jnp.exp(a_cum)  # (B, NC, H, T) decay from chunk start to token

    if init_state is None:
        init_state = jnp.zeros((B, H, N, P), jnp.float32)

    def step(carry, inp):
        state_c, decay_c, cm, din = inp
        y_inter = jnp.einsum("bhtn,bhnp->bhtp", cm * din[..., None], carry)
        carry = carry * decay_c[..., None, None] + state_c
        return carry, y_inter

    xs = (
        jnp.moveaxis(states, 1, 0),
        jnp.moveaxis(chunk_decay, 1, 0),
        jnp.moveaxis(ch, 1, 0),
        jnp.moveaxis(decay_in, 1, 0),
    )
    final_state, y_inter = jax.lax.scan(step, init_state, xs)
    y_inter = jnp.moveaxis(y_inter, 0, 1)  # (B, NC, H, T, P)

    y = (y_intra.astype(jnp.float32) + y_inter)
    y = y.transpose(0, 1, 3, 2, 4).reshape(B, S, H, P).astype(x.dtype)
    return y, final_state


def ssd_decode_step(
    state: jax.Array,  # (B, H, N, P)
    xt: jax.Array,     # (B, H, P)
    bt: jax.Array,     # (B, G, N)
    ct: jax.Array,     # (B, G, N)
    dtat: jax.Array,   # (B, H)
    dtt: jax.Array,    # (B, H)
) -> Tuple[jax.Array, jax.Array]:
    """O(1) single-token SSD update (serving path)."""
    H = state.shape[1]
    G = bt.shape[1]
    ratio = H // G
    bh = jnp.repeat(bt, ratio, axis=1).astype(jnp.float32)
    ch = jnp.repeat(ct, ratio, axis=1).astype(jnp.float32)
    decay = jnp.exp(dtat.astype(jnp.float32))[..., None, None]
    state = decay * state + jnp.einsum(
        "bhn,bhp->bhnp", bh * dtt.astype(jnp.float32)[..., None],
        xt.astype(jnp.float32)
    )
    y = jnp.einsum("bhn,bhnp->bhp", ch, state)
    return state, y.astype(xt.dtype)
