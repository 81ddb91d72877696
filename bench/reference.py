"""The plain Task Bench reference and the comparison that decides ``correct``.

Written from Task Bench's definition (Slaughter et al., SC'20) as the
configuration files state it, and independent of the program under test: a
graph of ``steps`` timesteps over ``width`` points; at t = 0 every task runs
its body on its initial state; at t >= 1 task p first takes the mean of the
outputs of its dependencies at t - 1 (the pattern's ``combine``, one module
per pattern under ``bench/patterns/``), then runs its body. The
compute_bound body is ``iterations`` steps of x <- a*x + b.

Everything is plain ``jax.numpy`` on whole arrays, in the dtype asked for:
the configuration's for the reference, the nearest precision below it
(``lower``) for the control.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

PATTERNS_DIR = Path(__file__).resolve().parent / "patterns"
#: the nearest float precision below each a configuration may state
LOWER = {"float64": "float32", "float32": "bfloat16"}


def lower(dtype):
    """The control's dtype for a configuration's ``dtype``."""
    return jnp.dtype(LOWER[jnp.dtype(dtype).name])


def load_pattern(name: str):
    """The reference module of a dependence pattern, found by its name."""
    path = PATTERNS_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reference for pattern {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_pattern_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.partial(jax.jit, static_argnames=(
    "combine", "steps", "kind", "iterations", "a", "b", "dtype"))
def run_graphs(inits, *, combine, steps, kind, iterations, a, b, dtype):
    """Final states of K graphs from their (K, W, P) initial states, computed
    in ``dtype`` and returned in the initial states' dtype."""

    def body(x):
        if kind == "empty" or iterations == 0:
            return x
        ca, cb = jnp.asarray(a, dtype), jnp.asarray(b, dtype)
        return jax.lax.fori_loop(0, iterations, lambda _, v: ca * v + cb, x)

    def one(x0):
        x = body(x0.astype(dtype))
        x = jax.lax.fori_loop(1, steps, lambda t, v: body(combine(v, t)), x)
        return x.astype(x0.dtype)

    return jax.vmap(one)(inits)


def _classes(x):
    """0 finite, 1 +inf, 2 -inf, 3 NaN."""
    return jnp.where(jnp.isnan(x), 3, jnp.where(
        jnp.isposinf(x), 1, jnp.where(jnp.isneginf(x), 2, 0)))


@jax.jit
def compare(out, ref):
    """(elements whose class differs, largest relative error over elements
    finite in both, elements finite in the reference)."""
    mismatch = jnp.sum(_classes(out) != _classes(ref))
    fin = jnp.isfinite(out) & jnp.isfinite(ref)
    err = jnp.where(fin, jnp.abs(out - ref), 0.0)
    rel = err / jnp.maximum(jnp.abs(jnp.where(fin, ref, 1.0)), 1e-30)
    return mismatch, jnp.max(rel), jnp.sum(jnp.isfinite(ref))
