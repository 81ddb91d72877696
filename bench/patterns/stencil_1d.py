"""Task Bench ``stencil_1d``: task p at t depends on {p-1, p, p+1} at t-1,
clipped to [0, W). The combine is the mean over the dependencies that
exist: three inside, two at either edge."""
import jax.numpy as jnp


def combine(x, t):
    del t  # the same dependencies at every step
    W = x.shape[0]
    zero = jnp.zeros_like(x[:1])
    left = jnp.concatenate([zero, x[:-1]])   # x[p-1]; none at p = 0
    right = jnp.concatenate([x[1:], zero])   # x[p+1]; none at p = W-1
    p = jnp.arange(W)[:, None]
    count = 3 - (p == 0).astype(x.dtype) - (p == W - 1).astype(x.dtype)
    return (left + x + right) / count
