"""The radix-2 FFT butterfly as a Task Bench pattern: task p at t depends
on {p, p XOR 2^k} at t-1, with k = (t-1) mod log2(W), so the levels rise
0, 1, ..., log2(W)-1 and wrap. The combine is the mean of the two. W is a
power of two, so every point has both dependencies. (Upstream Task Bench's
own ``fft`` dependence type is another graph; this is the butterfly of
Cooley and Tukey's radix-2 FFT, where stage k pairs p with p XOR 2^k.)

What a 1000-step output can show, and what it cannot. At level k the
mean makes rows p and p XOR 2^k exactly equal, so after one period
(log2(W) steps, 12 at W = 4096) every payload column is constant across
the rows and every later step keeps it so; the compute_bound body then
pulls each finite value to its fixed point 0.2 within about 155
iterations. So the output cannot show the partners used after the first
period, the order of the levels, the step count beyond one period, or the
body's iteration count. It can show the light cone of each non-finite
initial value: it reaches every row of its column only if every level of
the butterfly ran, a level left out leaves half of the column's rows in
the wrong class, and a swap along the payload axis, a lane mix-up or a
lost column carries non-finite values into columns where the reference
has none. Tests that compare the program's step with this one a timestep
at a time cover what the output cannot.
"""
import jax.numpy as jnp


def combine(x, t):
    W = x.shape[0]
    levels = W.bit_length() - 1
    if W != 1 << levels:
        raise ValueError(f"the butterfly needs a power-of-two width, not {W}")
    k = (t - 1) % max(1, levels)
    partner = jnp.bitwise_xor(jnp.arange(W), jnp.left_shift(1, k))
    return (x + x[partner]) / 2
