"""Shared grain-size task-body math for the Task Bench kernels.

One definition of each body, written on *values* (not Refs), so the same
function is used by

  * the runtime reference path (``repro.core.task_kernels``),
  * the standalone Pallas body kernels (``taskbench_compute.py`` and
    ``memory_bound_pallas`` below), and
  * the fused-timestep megakernel (``taskbench_step.py``),

so every runtime backend — jnp or Pallas — executes the identical op
sequence. The TEST oracles deliberately do NOT share this module:
``kernels/ref.py`` re-derives the semantics independently so parity tests
can catch a regression here.

This module depends only on jax — it sits at the bottom of the kernel
subsystem so both ``repro.core`` and ``repro.kernels`` may import it without
cycles.

Bodies (see the paper §6.1 and task_kernels.py for the overhead model):

  compute_bound  iterated elementwise FMA x <- A*x + B; |A| < 1 keeps any
                 grain size bounded while staying un-DCE-able.
  memory_bound   bytes-dominated scratch sweep: expand the payload into a
                 (scratch,) working set, read-modify-write it per iteration
                 (roll + add forces a full pass), reduce back to payload.
  empty          identity (pure runtime-overhead probe).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Contraction constants: x converges towards B/(1-A) = 0.2 without ever
# being constant-foldable (A, B are runtime scalars broadcast in).
FMA_A = 0.5
FMA_B = 0.1

LANE = 128
SUBLANE = 8


def fma_body(x: jax.Array, iterations: int) -> jax.Array:
    """Iterated FMA: x <- A*x + B, ``iterations`` times (trace-time loop-free)."""
    a = jnp.asarray(FMA_A, x.dtype)
    b = jnp.asarray(FMA_B, x.dtype)

    def body(_, v):
        return a * v + b

    return jax.lax.fori_loop(0, iterations, body, x)


def memory_sweep_body(x: jax.Array, iterations: int, scratch: int) -> jax.Array:
    """Bytes-dominated body: stream a scratch buffer ``iterations`` times.

    Each point expands its payload into a (scratch,) working set, sweeps it
    (read-modify-write) per iteration, then reduces back to payload size.
    """
    lead = x.shape[:-1]
    payload = x.shape[-1]
    reps = -(-scratch // payload)  # ceil
    buf = jnp.tile(x, lead and (1,) * len(lead) + (reps,) or (reps,))[..., :scratch]

    def body(i, b):
        # rotate + add: forces a full read and write of the buffer
        return jnp.roll(b, 1, axis=-1) + jnp.asarray(1e-6, b.dtype)

    buf = jax.lax.fori_loop(0, iterations, body, buf)
    # reduce back to payload: mean over the scratch window per payload slot
    pad = reps * payload - scratch
    if pad:  # a zero-width operand does not lower on Mosaic
        buf = jnp.concatenate([buf, jnp.zeros(lead + (pad,), buf.dtype)],
                              axis=-1)
    # a sum of static lane slices, not a (reps, payload) reshape: Mosaic
    # cannot split the lane axis. Accumulated in f32, as jnp.mean does.
    acc = buf[..., :payload].astype(jnp.float32)
    for r in range(1, reps):
        acc = acc + buf[..., r * payload:(r + 1) * payload].astype(jnp.float32)
    return (acc / reps).astype(buf.dtype)


def apply_body(x: jax.Array, kind: str, iterations: int, scratch: int) -> jax.Array:
    """Value-level body dispatch shared by the Pallas kernels."""
    if kind == "empty" or iterations == 0:
        return x
    if kind == "compute_bound":
        return fma_body(x, iterations)
    if kind == "memory_bound":
        return memory_sweep_body(x, iterations, scratch)
    raise ValueError(f"unknown kernel kind {kind!r}")


# --------------------------------------------------- standalone body kernels


def _memory_kernel(x_ref, o_ref, *, iterations: int, scratch: int, payload: int):
    if iterations == 0:  # same early-out as apply_body: the body is identity
        o_ref[...] = x_ref[...]
        return
    # The sweep mixes columns (roll), so it must run on the TRUE payload
    # slice — lane padding would leak zeros into real columns.
    x = x_ref[...][:, :payload]
    out = memory_sweep_body(x, iterations, scratch)
    o_ref[...] = jnp.pad(out, ((0, 0), (0, o_ref.shape[-1] - payload)))


@functools.partial(
    jax.jit, static_argnames=("iterations", "scratch", "block_rows", "interpret")
)
def memory_bound_pallas(
    x: jax.Array,
    iterations: int,
    scratch: int,
    *,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Scratch-sweep body over x: (rows, payload). Returns same shape/dtype.

    Pallas rendition of ``memory_sweep_body`` so ``use_pallas=True`` covers
    the memory-bound kernel kind too. The (block_rows, scratch) working set
    lives in VMEM for the whole sweep; rows are gridded so the working set
    stays under the VMEM budget at any row count.
    """
    if x.ndim != 2:
        raise ValueError(f"expected (rows, payload), got {x.shape}")
    rows, payload = x.shape

    # same policy as taskbench_step: the interpreter has no tile
    # constraints, and lane-padding would inflate the very copy traffic a
    # memory-bound body exists to measure
    lane, sublane = (1, 1) if interpret else (LANE, SUBLANE)
    pad_p = (-payload) % lane
    block_rows = max(sublane, min(block_rows, rows + (-rows) % sublane))
    pad_r = (-rows) % block_rows
    xp = jnp.pad(x, ((0, pad_r), (0, pad_p)))
    rp, pp = xp.shape

    out = pl.pallas_call(
        functools.partial(
            _memory_kernel, iterations=iterations, scratch=scratch, payload=payload
        ),
        grid=(rp // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, pp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, pp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, pp), x.dtype),
        interpret=interpret,
    )(xp)
    return out[:rows, :payload]
