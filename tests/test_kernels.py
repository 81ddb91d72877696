"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bodies import memory_bound_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_chunk_pallas
from repro.kernels.taskbench_compute import taskbench_compute_pallas
from repro.kernels import schedule
from repro.kernels.taskbench_step import (
    WEIGHT_DTYPE,
    finalize_weights,
    prepare_step_operands,
    taskbench_step_pallas,
)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------- taskbench


@pytest.mark.parametrize("rows,payload", [(4, 16), (32, 64), (100, 130),
                                          (7, 5), (256, 128)])
@pytest.mark.parametrize("iters", [0, 1, 7, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_taskbench_compute_sweep(rows, payload, iters, dtype):
    x = jax.random.uniform(jax.random.PRNGKey(0), (rows, payload),
                           jnp.float32).astype(dtype)
    got = taskbench_compute_pallas(x, iters, interpret=True)
    want = ref.taskbench_compute_ref(x, iters)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol(dtype))


def test_taskbench_block_rows_invariance():
    x = jax.random.uniform(jax.random.PRNGKey(1), (64, 96))
    a = taskbench_compute_pallas(x, 9, block_rows=8, interpret=True)
    b = taskbench_compute_pallas(x, 9, block_rows=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("rows,payload", [(4, 16), (33, 70), (100, 130)])
@pytest.mark.parametrize("iters,scratch", [(0, 64), (3, 64), (7, 100)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_taskbench_memory_sweep(rows, payload, iters, scratch, dtype):
    """memory_bound scratch-sweep body: Pallas vs jnp oracle."""
    x = jax.random.uniform(jax.random.PRNGKey(15), (rows, payload),
                           jnp.float32, 0.1, 1.0).astype(dtype)
    got = memory_bound_pallas(x, iters, scratch, interpret=True)
    want = ref.taskbench_memory_ref(x, iters, scratch)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol(dtype))


# ------------------------------------------------- fused-timestep megakernel


def _random_step_operands(key, K, S, W, D, zero_dep_rows=True):
    """Padded (idx, wgt) with random dep sets (incl. some zero-dep rows)."""
    rng = np.random.default_rng(key)
    idxs, wgts = [], []
    for k in range(K):
        dep_lists = []
        for p in range(W):
            n = int(rng.integers(0, D + 1))
            if zero_dep_rows and p % 5 == 0:
                n = 0
            dep_lists.append(list(rng.integers(0, S, n)))
        i, w = prepare_step_operands(dep_lists, W, list(range(min(W, S))) +
                                     [0] * max(0, W - S))
        pad = D - i.shape[1]
        idxs.append(np.pad(i, ((0, 0), (0, pad))))
        wgts.append(np.pad(w, ((0, 0), (0, pad))))
    return jnp.asarray(np.stack(idxs)), jnp.asarray(np.stack(wgts))


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("S,W,payload,D", [
    (16, 16, 64, 3),    # square, aligned payload
    (20, 16, 13, 5),    # halo-extended src, ragged payload
    (7, 7, 130, 2),     # ragged rows, payload > one lane
])
@pytest.mark.parametrize("kind,iters", [("compute_bound", 8),
                                        ("memory_bound", 3), ("empty", 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_taskbench_step_parity_sweep(K, S, W, payload, D, kind, iters, dtype):
    """The megakernel (interpret) vs the pure-jnp step oracle: all kernel
    kinds x dtypes x ragged shapes x ensemble K."""
    src = jax.random.uniform(jax.random.PRNGKey(16), (K, S, payload),
                             jnp.float32, 0.1, 1.0).astype(dtype)
    idx, wgt = _random_step_operands(17, K, S, W, D)
    got = taskbench_step_pallas(src, idx, wgt, kind=kind, iterations=iters,
                                scratch=50, interpret=True)
    want = ref.taskbench_step_ref(src, idx, wgt, kind=kind, iterations=iters,
                                  scratch=50)
    assert got.shape == (K, W, payload) and got.dtype == src.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol(dtype))


def test_taskbench_step_combine_modes_agree():
    """gather vs onehot must be numerically interchangeable."""
    K, S, W, P, D = 2, 12, 12, 24, 4
    src = jax.random.uniform(jax.random.PRNGKey(18), (K, S, P),
                             jnp.float32, 0.1, 1.0)
    idx, wgt = _random_step_operands(19, K, S, W, D)
    outs = [
        taskbench_step_pallas(src, idx, wgt, kind="compute_bound",
                              iterations=5, combine=mode, interpret=True)
        for mode in ("gather", "onehot")
    ]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-5, atol=1e-6)


def test_taskbench_step_window_matches_gather():
    """Window mode (shifted-slice FMAs) == gather mode on the same stencil."""
    K, B, H, P = 2, 16, 1, 10
    S = B + 2 * H
    src = jax.random.uniform(jax.random.PRNGKey(20), (K, S, P),
                             jnp.float32, 0.1, 1.0)
    # stencil window: every row averages offsets {-1, 0, +1}
    wgt_win = jnp.full((K, B, 2 * H + 1), 1.0 / 3.0, jnp.float32)
    idx_win = jnp.zeros((K, B, 2 * H + 1), jnp.int32)
    got = taskbench_step_pallas(src, idx_win, wgt_win, kind="compute_bound",
                                iterations=4, combine="window", interpret=True)
    # same dataflow via explicit gather operands
    rows = jnp.arange(B)
    idx_g = jnp.stack([rows, rows + 1, rows + 2], axis=1)[None].repeat(K, 0)
    want = taskbench_step_pallas(src, idx_g.astype(jnp.int32), wgt_win,
                                 kind="compute_bound", iterations=4,
                                 combine="gather", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("steps_per_launch", [1, 3])
def test_taskbench_step_gather_needs_interpret_mode(steps_per_launch):
    """A row gather has no Mosaic lowering: asked for on the chip path
    (interpret=False) it fails up front and names onehot."""
    K, S, W, P, D = 1, 12, 12, 8, 3
    src = jnp.ones((K, S, P))
    idx, wgt = _random_step_operands(23, K, S, W, D)
    act = jnp.ones((K, steps_per_launch)) if steps_per_launch > 1 else None
    with pytest.raises(ValueError, match="onehot"):
        taskbench_step_pallas(src, idx, wgt, act, combine="gather",
                              steps_per_launch=steps_per_launch,
                              interpret=False)


def test_taskbench_step_block_rows_invariance():
    K, S, W, P, D = 1, 32, 32, 16, 3
    src = jax.random.uniform(jax.random.PRNGKey(21), (K, S, P),
                             jnp.float32, 0.1, 1.0)
    idx, wgt = _random_step_operands(22, K, S, W, D)
    a = taskbench_step_pallas(src, idx, wgt, iterations=6, block_rows=8,
                              interpret=True)
    b = taskbench_step_pallas(src, idx, wgt, iterations=6, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ------------------------------------------ temporal-blocked megakernel


def _periodic_ext(state, depth):
    """Deep-halo extend a (K, W, P) state periodically (1-device wrap)."""
    K, W, P = state.shape
    ids = (np.arange(-depth, W + depth)) % W
    return state[:, ids, :]


def _stencil_window_weights(W, halo):
    """Per-global-row mean-over-{-1,0,1} weights, full (W, 2h+1) table."""
    return np.full((W, 2 * halo + 1), 1.0 / (2 * halo + 1), np.float32)


@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("S", [2, 5])
@pytest.mark.parametrize("combine", ["window", "gather", "onehot"])
@pytest.mark.parametrize("kind,iters", [("compute_bound", 3),
                                        ("memory_bound", 2), ("empty", 0)])
def test_taskbench_step_blocked_matches_iterated_single(K, S, combine,
                                                        kind, iters):
    """steps_per_launch=S on a depth-S*h extended buffer == S invocations
    of the single-step kernel, for every combine mode and kernel kind."""
    W, P, h = 12, 10, 1
    state = jax.random.uniform(jax.random.PRNGKey(30), (K, W, P),
                               jnp.float32, 0.1, 1.0)
    wfull = _stencil_window_weights(W, h)

    # reference: iterate the S=1 kernel (old contract) S times
    ref = state
    wgt1 = jnp.asarray(np.broadcast_to(wfull, (K, W, 3)).copy())
    rows = jnp.arange(W)
    idx1 = jnp.stack([rows, rows + 1, rows + 2], 1)[None].repeat(K, 0)
    for _ in range(S):
        ext = jnp.asarray(_periodic_ext(np.asarray(ref), h))
        ref = taskbench_step_pallas(
            ext, idx1.astype(jnp.int32), wgt1, kind=kind, iterations=iters,
            scratch=30, combine="gather", interpret=True)

    # blocked: square (K, M, *) operands
    depth = S * h
    M = W + 2 * depth
    gids = (np.arange(-depth, W + depth)) % W
    wext = jnp.asarray(np.broadcast_to(wfull[gids], (K, M, 3)).copy())
    rel = np.tile(np.array([-1, 0, 1], np.int32), (M, 1))
    iabs = np.clip(rel + np.arange(M)[:, None], 0, M - 1).astype(np.int32)
    iabs = jnp.asarray(np.broadcast_to(iabs, (K, M, 3)).copy())
    act = jnp.ones((K, S), jnp.float32)
    ext = jnp.asarray(_periodic_ext(np.asarray(state), depth))
    out = taskbench_step_pallas(
        ext, iabs, wext, act, kind=kind, iterations=iters, scratch=30,
        combine=combine, steps_per_launch=S, interpret=True)
    got = out[:, depth:depth + W]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_taskbench_step_blocked_act_mask_freezes_depths():
    """act encodes per-member inner-step horizons: member k with m active
    depths must equal iterating the single-step kernel m times."""
    K, W, P, h, S = 3, 8, 6, 1, 4
    state = jax.random.uniform(jax.random.PRNGKey(31), (K, W, P),
                               jnp.float32, 0.1, 1.0)
    wfull = _stencil_window_weights(W, h)
    depth = S * h
    M = W + 2 * depth
    gids = (np.arange(-depth, W + depth)) % W
    wext = jnp.asarray(np.broadcast_to(wfull[gids], (K, M, 3)).copy())
    idx = jnp.zeros((K, 1, 1), jnp.int32)
    # member k executes k+1 of the 4 depths
    act = jnp.asarray((np.arange(S)[None, :]
                       < np.arange(1, K + 1)[:, None]).astype(np.float32))
    ext = jnp.asarray(_periodic_ext(np.asarray(state), depth))
    out = taskbench_step_pallas(
        ext, idx, wext, act, kind="compute_bound", iterations=2,
        combine="window", steps_per_launch=S, interpret=True)
    got = out[:, depth:depth + W]

    wgt1 = jnp.asarray(wfull)[None]
    rows = jnp.arange(W)
    idx1 = jnp.stack([rows, rows + 1, rows + 2], 1)[None].astype(jnp.int32)
    for k in range(K):
        ref = state[k:k + 1]
        for _ in range(k + 1):
            ext1 = jnp.asarray(_periodic_ext(np.asarray(ref), h))
            ref = taskbench_step_pallas(
                ext1, idx1, wgt1, kind="compute_bound", iterations=2,
                combine="gather", interpret=True)
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[0]),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"member {k}")


def test_taskbench_step_blocked_requires_act_and_square_operands():
    src = jnp.ones((1, 10, 4))
    wgt = jnp.ones((1, 10, 3)) / 3
    idx = jnp.zeros((1, 10, 3), jnp.int32)
    with pytest.raises(ValueError, match="act"):
        taskbench_step_pallas(src, idx, wgt, steps_per_launch=3,
                              interpret=True)
    act = jnp.ones((1, 3), jnp.float32)
    with pytest.raises(ValueError, match="square"):
        taskbench_step_pallas(src, idx, jnp.ones((1, 8, 3)) / 3, act,
                              steps_per_launch=3, interpret=True)


def test_taskbench_step_pair_combine_matches_gather():
    """pair mode ([x | partner] halves, elementwise (a+b)*0.5) must be
    bit-identical to gathering {i, W+i} at weight 0.5 from the same
    stacked buffer — the stride plan's gather-free butterfly lowering."""
    K, W, P = 2, 8, 6
    x = jax.random.uniform(jax.random.PRNGKey(40), (K, W, P),
                           jnp.float32, 0.1, 1.0)
    partner = x[:, ::-1]  # any permutation works; the kernel just pairs
    src = jnp.concatenate([x, partner], axis=1)  # (K, 2W, P)
    dummy_i = jnp.zeros((K, 1, 1), jnp.int32)
    dummy_w = jnp.zeros((K, W, 1), jnp.float32)
    got = taskbench_step_pallas(src, dummy_i, dummy_w, kind="compute_bound",
                                iterations=3, combine="pair", interpret=True)
    rows = jnp.arange(W)
    idx = jnp.broadcast_to(jnp.stack([rows, W + rows], 1), (K, W, 2))
    wgt = jnp.full((K, W, 2), 0.5, jnp.float32)
    want = taskbench_step_pallas(src, idx.astype(jnp.int32), wgt,
                                 kind="compute_bound", iterations=3,
                                 combine="gather", interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # contract violations fail loudly
    with pytest.raises(ValueError, match="pair"):
        taskbench_step_pallas(x, dummy_i, dummy_w, combine="pair",
                              interpret=True)  # src not [x | partner]
    act = jnp.ones((K, 2), jnp.float32)
    with pytest.raises(ValueError, match="per-step"):
        taskbench_step_pallas(src, dummy_i, dummy_w, act, combine="pair",
                              steps_per_launch=2, interpret=True)


# -------------------------------------- time-varying per-depth tables


@pytest.mark.parametrize("combine", ["gather", "onehot"])
def test_taskbench_step_blocked_time_varying_tables(combine):
    """(K, S, M, D) tables — one per inner depth — must equal iterating
    the single-step kernel with each depth's own table (the butterfly /
    rotation contract: XOR stride 2^d at depth d here). The working
    buffer is exactly closed under every table (global rows), so there is
    no valid-span shrink and the whole buffer is exact; weights of 0.5
    keep the check bitwise."""
    K, W, P, S = 2, 8, 6, 3
    state = jax.random.uniform(jax.random.PRNGKey(32), (K, W, P),
                               jnp.float32, 0.1, 1.0)
    rows = np.arange(W, dtype=np.int32)
    tabs = np.stack([np.stack([rows, rows ^ (1 << d)], 1)
                     for d in range(S)])  # (S, W, 2)
    idx = np.broadcast_to(tabs, (K, S, W, 2)).copy()
    wgt = np.full((K, S, W, 2), 0.5, np.float32)
    act = jnp.ones((K, S), jnp.float32)
    out = taskbench_step_pallas(
        state, jnp.asarray(idx), jnp.asarray(wgt), act,
        kind="compute_bound", iterations=3, combine=combine,
        steps_per_launch=S, interpret=True)
    ref = state
    for d in range(S):
        ref = taskbench_step_pallas(
            ref, jnp.asarray(idx[:, d]), jnp.asarray(wgt[:, d]),
            kind="compute_bound", iterations=3, combine=combine,
            interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_taskbench_step_time_varying_act_mask_freezes_depths():
    """The act machinery is UNCHANGED under time-varying tables: member k
    executing only m depths equals iterating the per-depth tables m
    times."""
    K, W, P, S = 3, 8, 4, 3
    state = jax.random.uniform(jax.random.PRNGKey(33), (K, W, P),
                               jnp.float32, 0.1, 1.0)
    rows = np.arange(W, dtype=np.int32)
    tabs = np.stack([np.stack([rows, rows ^ (1 << d)], 1)
                     for d in range(S)])
    idx = jnp.asarray(np.broadcast_to(tabs, (K, S, W, 2)).copy())
    wgt = jnp.full((K, S, W, 2), 0.5, jnp.float32)
    act = jnp.asarray((np.arange(S)[None, :]
                       < np.arange(1, K + 1)[:, None]).astype(np.float32))
    out = taskbench_step_pallas(
        state, idx, wgt, act, kind="compute_bound", iterations=2,
        combine="onehot", steps_per_launch=S, interpret=True)
    for k in range(K):
        ref = state[k:k + 1]
        for d in range(k + 1):
            ref = taskbench_step_pallas(
                ref, idx[k:k + 1, d], wgt[k:k + 1, d],
                kind="compute_bound", iterations=2, combine="onehot",
                interpret=True)
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[0]),
                                      err_msg=f"member {k}")


def test_taskbench_step_time_varying_validation():
    src = jnp.ones((1, 8, 4))
    idx4 = jnp.zeros((1, 3, 8, 2), jnp.int32)
    wgt4 = jnp.full((1, 3, 8, 2), 0.5)
    act = jnp.ones((1, 3), jnp.float32)
    # window mode has no time-varying form
    with pytest.raises(ValueError, match="window"):
        taskbench_step_pallas(src, idx4, wgt4, act, combine="window",
                              steps_per_launch=3, interpret=True)
    # depth axis must match steps_per_launch
    with pytest.raises(ValueError, match="time-varying"):
        taskbench_step_pallas(src, idx4, wgt4, jnp.ones((1, 2)),
                              combine="onehot", steps_per_launch=2,
                              interpret=True)
    # 4-D tables make no sense on the single-step path
    with pytest.raises(ValueError, match="steps_per_launch"):
        taskbench_step_pallas(src, idx4, wgt4, combine="onehot",
                              interpret=True)


# ------------------------------------------ pipelined phase entry points


@pytest.mark.parametrize("tail", [0, 2])
def test_taskbench_phase_split_matches_full_blocked(tail):
    """interior + boundary entry points == the one-buffer blocked launch:
    stitching [left_out | interior | right_out] must be bit-identical to
    slicing the owned rows out of the full deep-halo kernel, including a
    masked tail (the hetero/final-launch case)."""
    from repro.kernels.taskbench_step import (taskbench_step_boundary,
                                              taskbench_step_interior)
    K, W, P, h, S = 2, 24, 6, 1, 4
    depth = S * h
    state = jax.random.uniform(jax.random.PRNGKey(32), (K, W, P),
                               jnp.float32, 0.1, 1.0)
    wfull = _stencil_window_weights(W, h)
    gids = (np.arange(-depth, W + depth)) % W
    wext = jnp.asarray(np.broadcast_to(wfull[gids], (K, W + 2 * depth, 3)).copy())
    idx = jnp.zeros((K, 1, 1), jnp.int32)
    act = jnp.asarray(np.broadcast_to(
        (np.arange(S) < S - tail).astype(np.float32), (K, S)).copy())
    kw = dict(kind="compute_bound", iterations=2, combine="window",
              steps_per_launch=S, interpret=True)

    ext = jnp.asarray(_periodic_ext(np.asarray(state), depth))
    full = taskbench_step_pallas(ext, idx, wext, act, **kw)[:, depth:depth + W]

    hl, hr = ext[:, :depth], ext[:, W + depth:]
    left = jnp.concatenate([hl, state[:, :2 * depth]], axis=1)
    right = jnp.concatenate([state[:, W - 2 * depth:], hr], axis=1)
    w_bnd = jnp.concatenate(
        [wext[:, :3 * depth], wext[:, W - depth:]], axis=1)
    blo, bro = taskbench_step_boundary(
        left, right, idx, w_bnd, act, depth=depth, **kw)
    mid = taskbench_step_interior(
        state, idx, wext[:, depth:depth + W], act, depth=depth, **kw)
    got = jnp.concatenate([blo, mid, bro], axis=1)
    assert np.array_equal(np.asarray(got), np.asarray(full)), \
        f"phase split changed bits (tail={tail})"


def test_taskbench_phase_entry_points_validate_shapes():
    from repro.kernels.taskbench_step import (taskbench_step_boundary,
                                              taskbench_step_interior)
    act = jnp.ones((1, 2), jnp.float32)
    idx = jnp.zeros((1, 1, 1), jnp.int32)
    with pytest.raises(ValueError, match="interior"):
        taskbench_step_interior(jnp.ones((1, 8, 4)), idx,
                                jnp.ones((1, 8, 3)), act, depth=4,
                                combine="window", steps_per_launch=2,
                                interpret=True)
    with pytest.raises(ValueError, match="boundary"):
        taskbench_step_boundary(jnp.ones((1, 8, 4)), jnp.ones((1, 6, 4)),
                                idx, jnp.ones((1, 12, 3)), act, depth=2,
                                combine="window", steps_per_launch=2,
                                interpret=True)


# ----------------------------------------------------------- schedule tuner


def test_schedule_choose_respects_vmem_budget():
    # a tiny budget forces shallow launches; a huge one allows the deepest
    tiny = schedule.choose_steps_per_launch(
        block=1024, radius=8, payload=512, vmem_budget=1 << 20)
    huge = schedule.choose_steps_per_launch(
        block=1024, radius=8, payload=512, vmem_budget=1 << 30)
    assert 1 <= tiny < huge <= max(schedule.CANDIDATES)
    # working-set model is monotone in S
    sizes = [schedule.blocked_working_set_bytes(256, 2, s, 64)
             for s in (1, 2, 4, 8)]
    assert sizes == sorted(sizes)


def test_schedule_accounts_for_combine_mode_intermediates():
    """gather/onehot carry bigger working sets than window, so 'auto' must
    pick shallower (or equal) depths for them at the same budget."""
    kw = dict(block=1024, radius=8, payload=512, vmem_budget=64 << 20)
    win = schedule.choose_steps_per_launch(combine="window", **kw)
    gat = schedule.choose_steps_per_launch(combine="gather", **kw)
    one = schedule.choose_steps_per_launch(combine="onehot", **kw)
    assert one <= gat <= win
    assert one < win  # the onehot expansion must actually bite
    for s in (1, 4):
        base = schedule.blocked_working_set_bytes(1024, 8, s, 512)
        assert schedule.blocked_working_set_bytes(
            1024, 8, s, 512, combine="gather") > base
        assert schedule.blocked_working_set_bytes(
            1024, 8, s, 512, combine="onehot") > base


def test_schedule_caps_depth_at_combine_steps():
    assert schedule.choose_steps_per_launch(
        block=64, radius=1, payload=64, total_steps=5) <= 4
    assert schedule.resolve_steps_per_launch(
        16, block=64, radius=1, payload=64, total_steps=5) == 4


def test_schedule_resolve_values():
    kw = dict(block=64, radius=1, payload=64, total_steps=100)
    assert schedule.resolve_steps_per_launch(None, **kw) == 1
    assert schedule.resolve_steps_per_launch(1, **kw) == 1
    assert schedule.resolve_steps_per_launch(8, **kw) == 8
    auto = schedule.resolve_steps_per_launch("auto", **kw)
    assert auto == schedule.choose_steps_per_launch(**kw)
    with pytest.raises(ValueError):
        schedule.resolve_steps_per_launch(-2, **kw)


def test_schedule_accounts_for_act_and_idx_operands():
    """The VMEM model charges the act mask (S f32s even at radius 0, where
    the buffer itself is S-invariant) and, for the non-window combines, the
    per-row int32 idx table on top of gather's row intermediate."""
    for s in (1, 2, 4, 8):
        assert (schedule.blocked_working_set_bytes(64, 0, s + 1, 64)
                - schedule.blocked_working_set_bytes(64, 0, s, 64)) == 4
    m = 256 + 2 * 4 * 2
    window = 2 * 2 + 1
    base = schedule.blocked_working_set_bytes(256, 2, 4, 64)
    gat = schedule.blocked_working_set_bytes(256, 2, 4, 64, combine="gather")
    gathered_rows = m * window * 128 * 4  # the (m, window, payload) gather
    assert gat - base - gathered_rows == m * window * 4  # idx table itself


def test_schedule_pipeline_working_set_and_covering():
    """Pipelined residency = max(interior, boundary program) + double-
    buffered halo slots — smaller than the monolithic serial buffer at
    wide blocks; empty-interior shapes fall back to serial accounting.
    The covering rule admits S=8 at block 256 (r=1) but rejects S=16
    (boundary work outgrows the exchange) and tiny blocks (nothing to
    hide under), and 'auto' follows it."""
    serial = schedule.blocked_working_set_bytes(1024, 8, 8, 512)
    piped = schedule.blocked_working_set_bytes(1024, 8, 8, 512,
                                               pipeline=True)
    assert piped < serial
    assert schedule.blocked_working_set_bytes(
        64, 8, 8, 512, pipeline=True) == schedule.blocked_working_set_bytes(
        64, 8, 8, 512)  # block 64 <= 2*64: no interior, serial layout
    assert schedule.pipeline_interior_covers_exchange(256, 1, 8)
    assert not schedule.pipeline_interior_covers_exchange(256, 1, 16)
    assert not schedule.pipeline_interior_covers_exchange(64, 1, 8)
    kw = dict(block=256, radius=1, payload=64, total_steps=200)
    assert schedule.choose_steps_per_launch(**kw) == 16
    assert schedule.choose_steps_per_launch(pipeline=True, **kw) == 8
    # no covering candidate -> fall back to the deepest fitting depth
    assert schedule.choose_steps_per_launch(
        block=64, radius=1, payload=64, total_steps=200, pipeline=True) == 16


def test_schedule_auto_budgets_the_schedule_it_executes():
    """A pipeline=True pick whose interior does NOT cover the exchange
    runs the SERIAL schedule, so the fallback depth must be validated
    against the serial (monolithic-buffer) sizing — not the smaller
    pipelined one (it once wasn't: block=224/r=2/payload=1024/gather
    picked S=2 whose serial working set overflowed the default budget)."""
    for combine in ("window", "gather", "onehot"):
        for radius in (1, 2, 4, 8):
            for block in (32, 64, 224, 256, 1024):
                for payload in (64, 256, 1024):
                    s = schedule.choose_steps_per_launch(
                        block=block, radius=radius, payload=payload,
                        combine=combine, pipeline=True)
                    if s <= 1:  # S=1 is the per-step path: no blocked buffer
                        continue
                    cov = schedule.pipeline_interior_covers_exchange(
                        block, radius, s)
                    ws = schedule.blocked_working_set_bytes(
                        block, radius, s, payload, combine=combine,
                        pipeline=cov)
                    assert ws <= schedule.DEFAULT_VMEM_BUDGET, \
                        (combine, radius, block, payload, s)


def test_schedule_gathered_working_set_accounting():
    """The all-gather plan's budget charges the full-width buffer AND the
    time-varying per-depth tables (S stacked (W, D) idx+wgt pairs — the
    operands the halo budget never carried)."""
    base = schedule.gathered_working_set_bytes(256, 2, 4, 64)
    deeper = schedule.gathered_working_set_bytes(256, 2, 8, 64)
    # exactly 4 more (W, D) int32+f32 tables plus 4 act floats
    assert deeper - base == 4 * 256 * 2 * 8 + 4 * 4
    static = schedule.gathered_working_set_bytes(256, 2, 8, 64,
                                                 time_varying=False)
    assert static < deeper  # static tables: one depth's tables, any S
    # combine intermediates: onehot holds the (W, W) matrix + its
    # (W, D, W) expansion; gather the (W, D, Pp) gathered rows
    one = schedule.gathered_working_set_bytes(256, 2, 4, 64)
    gat = schedule.gathered_working_set_bytes(256, 2, 4, 64,
                                              combine="gather")
    assert one - gat == (256 * 256 * 4 + 256 * 2 * 256 * 4
                         - 256 * 2 * 128 * 4)


def test_schedule_gathered_pays_off_rule():
    """Replication S*(W - B) must stay under the saved exchanges
    (S-1)*X: one device (W == B) always pays, wide replication never."""
    assert schedule.gathered_pays_off(512, 512, 16)  # 1 device: free
    assert schedule.gathered_pays_off(512, 128, 8)   # 3072 <= 3584
    assert not schedule.gathered_pays_off(1024, 256, 8)  # 6144 > 3584
    assert not schedule.gathered_pays_off(512, 128, 1)  # S=1 saves nothing


def test_schedule_gathered_choose_and_resolve():
    kw = dict(width=64, block=16, max_deps=2, payload=8)
    s = schedule.choose_steps_per_launch_gathered(total_steps=50, **kw)
    assert s > 1
    assert schedule.resolve_steps_per_launch_gathered(
        "auto", total_steps=50, **kw) == s
    assert schedule.resolve_steps_per_launch_gathered(None, **kw) == 1
    assert schedule.resolve_steps_per_launch_gathered(1, **kw) == 1
    # explicit depths clamp to the combine-step count
    assert schedule.resolve_steps_per_launch_gathered(
        8, total_steps=5, **kw) == 4
    with pytest.raises(ValueError):
        schedule.resolve_steps_per_launch_gathered(-1, **kw)
    # a pattern that can never pay (replication too wide at every S)
    assert schedule.choose_steps_per_launch_gathered(
        width=4096, block=32, max_deps=2, payload=8, total_steps=50) == 1


def test_schedule_exchange_row_steps_env_override(monkeypatch):
    """ROADMAP's per-platform re-calibration knob: the exchange-cost
    constant is env-overridable and consulted LIVE by every covering /
    pays-off rule — no reimport, invalid values fail loudly."""
    monkeypatch.delenv("REPRO_PIPELINE_EXCHANGE_ROW_STEPS", raising=False)
    assert schedule.exchange_row_steps() == \
        schedule.PIPELINE_EXCHANGE_ROW_STEPS
    assert schedule.gathered_pays_off(512, 128, 8)
    assert schedule.pipeline_interior_covers_exchange(256, 1, 8)
    monkeypatch.setenv("REPRO_PIPELINE_EXCHANGE_ROW_STEPS", "64")
    assert schedule.exchange_row_steps() == 64
    assert not schedule.gathered_pays_off(512, 128, 8)  # 3072 > 7*64
    assert not schedule.pipeline_interior_covers_exchange(256, 1, 8)
    monkeypatch.setenv("REPRO_PIPELINE_EXCHANGE_ROW_STEPS", "100000")
    assert schedule.gathered_pays_off(1024, 256, 8)
    for bad in ("0", "-5", "many"):
        monkeypatch.setenv("REPRO_PIPELINE_EXCHANGE_ROW_STEPS", bad)
        with pytest.raises(ValueError):
            schedule.exchange_row_steps()


def test_finalize_weights_single_rounding():
    """The one weight-precision policy: f64 accumulation, one f32 round."""
    acc = np.array([[1.0 / 3.0 + 1.0 / 3.0 + 1.0 / 3.0]], np.float64)
    out = finalize_weights(acc)
    assert out.dtype == WEIGHT_DTYPE
    np.testing.assert_array_equal(
        out, np.asarray(acc, np.float64).astype(np.float32))
    # prepare_step_operands flows through the same policy
    _, wgt = prepare_step_operands([[0, 1, 2]], 1, [0])
    assert wgt.dtype == WEIGHT_DTYPE


def test_prepare_step_operands_self_pads_and_normalizes():
    idx, wgt = prepare_step_operands([[1, 2], [0], [], [3, 3]], 4,
                                     [0, 1, 2, 3])
    np.testing.assert_array_equal(idx, [[1, 2], [0, 0], [2, 0], [3, 3]])
    np.testing.assert_allclose(wgt, [[0.5, 0.5], [1.0, 0.0], [1.0, 0.0],
                                     [0.5, 0.5]])
    assert wgt.sum(axis=1).tolist() == [1.0, 1.0, 1.0, 1.0]


# ----------------------------------------------------------------- rmsnorm


@pytest.mark.parametrize("rows,d", [(8, 64), (33, 100), (5, 1536), (128, 257)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(rows, d, dtype):
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (rows, d), jnp.float32).astype(dtype)
    w = jax.random.uniform(jax.random.PRNGKey(3), (d,), jnp.float32,
                           0.5, 1.5).astype(dtype)
    got = rmsnorm_pallas(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **tol(dtype))


# --------------------------------------------------------------- attention


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,D", [
    (1, 4, 4, 32, 32, 32),     # MHA
    (2, 8, 2, 64, 64, 16),     # GQA 4:1
    (1, 2, 1, 40, 72, 64),     # ragged lengths (padding paths)
    (1, 4, 2, 128, 128, 128),  # hardware-aligned
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 16), (False, 0)])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Sk, D, causal, window):
    if not causal and Sq != Sk:
        pytest.skip("non-causal ragged not used (cross-attn is Sq!=Sk but "
                    "handled below)")
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (B, Hq, Sq, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, Hkv, Sk, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, Hkv, Sk, D), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 blk_q=32, blk_k=32, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_cross_no_causal():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, 4, 48, 32))
    k = jax.random.normal(keys[1], (2, 2, 80, 32))
    v = jax.random.normal(keys[2], (2, 2, 80, 32))
    got = flash_attention_pallas(q, k, v, causal=False, blk_q=16, blk_k=32,
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (1, 2, 64, 64)).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, 2, 64, 64)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, 2, 64, 64)).astype(jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


# ----------------------------------------------------- chunked attention


@pytest.mark.parametrize("Sq,Sk,blk", [(64, 64, 16), (48, 80, 32),
                                       (128, 128, 128), (100, 36, 16)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24), (False, 0)])
def test_chunked_attention_matches_dense(Sq, Sk, blk, causal, window):
    B, Hq, Hkv, D = 2, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(keys[0], (B, Hq, Sq, D))
    k = jax.random.normal(keys[1], (B, Hkv, Sk, D))
    v = jax.random.normal(keys[2], (B, Hkv, Sk, D))
    got = ref.chunked_attention_ref(q, k, v, causal=causal, window=window,
                                    blk=blk)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_chunked_attention_gradients_match_dense():
    """The chunked path is the TRAIN implementation for long sequences — its
    gradients must match the dense oracle's."""
    B, Hq, Hkv, S, D = 1, 2, 1, 64, 16
    keys = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(keys[0], (B, Hq, S, D))
    k = jax.random.normal(keys[1], (B, Hkv, S, D))
    v = jax.random.normal(keys[2], (B, Hkv, S, D))

    def loss_chunked(q, k, v):
        return jnp.sum(ref.chunked_attention_ref(q, k, v, blk=16) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v) ** 2)

    g1 = jax.grad(loss_chunked, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_chunked_attention_q_offset():
    """q_offset shifts causal/window masks (cached decode prefill chunks)."""
    B, H, S, D = 1, 2, 32, 8
    keys = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(keys[0], (B, H, 8, D))
    k = jax.random.normal(keys[1], (B, H, S, D))
    v = jax.random.normal(keys[2], (B, H, S, D))
    got = ref.chunked_attention_ref(q, k, v, q_offset=24, blk=8)
    want = ref.attention_ref(q, k, v, q_offset=24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------- decode attention


@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (2, 4, 4, 64, 32),
    (3, 8, 2, 100, 64),
    (1, 4, 1, 513, 128),
])
@pytest.mark.parametrize("window", [0, 32])
def test_decode_attention_sweep(B, Hq, Hkv, S, D, window):
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (B, Hq, D))
    kc = jax.random.normal(keys[1], (B, Hkv, S, D))
    vc = jax.random.normal(keys[2], (B, Hkv, S, D))
    lengths = jax.random.randint(keys[3], (B,), 1, S + 1, jnp.int32)
    got, m, l = decode_attention_pallas(q, kc, vc, lengths, window=window,
                                        blk_s=64, interpret=True)
    want, m_ref, l_ref = ref.decode_attention_ref(
        q, kc, vc, lengths, window=window, return_stats=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # softmax stats must match too (they feed the cross-shard combine)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref),
                               rtol=1e-4, atol=1e-4)


def test_decode_attention_zero_length_is_safe():
    B, Hq, Hkv, S, D = 2, 2, 2, 32, 16
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(keys[0], (B, Hq, D))
    kc = jax.random.normal(keys[1], (B, Hkv, S, D))
    vc = jax.random.normal(keys[2], (B, Hkv, S, D))
    lengths = jnp.array([0, 5], jnp.int32)
    got, m, l = decode_attention_pallas(q, kc, vc, lengths, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert float(l[0].sum()) == 0.0  # fully-masked row signals empty


# ----------------------------------------------------------------------- SSD


@pytest.mark.parametrize("BC,H,G,T,P,N", [
    (2, 2, 1, 16, 8, 8),
    (3, 4, 2, 32, 64, 16),
    (1, 2, 2, 128, 64, 128),
])
def test_ssd_chunk_sweep(BC, H, G, T, P, N):
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(keys[0], (BC, H, T, P))
    b = jax.random.normal(keys[1], (BC, G, T, N)) * 0.3
    c = jax.random.normal(keys[2], (BC, G, T, N)) * 0.3
    dta = -jax.random.uniform(keys[3], (BC, H, T), minval=0.01, maxval=0.3)
    dt = jax.random.uniform(keys[4], (BC, H, T), minval=0.1, maxval=1.0)
    y, s = ssd_chunk_pallas(x, b, c, dta, dt, interpret=True)
    y_ref, s_ref = ref.ssd_chunk_ref(x, b, c, dta, dt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunked_equals_sequential(chunk):
    """Chunked SSD (the paper-of-the-arch's core identity) == token-by-token
    recurrence, for any chunk size."""
    B, S, H, G, P, N = 2, 64, 2, 1, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(10), 5)
    x = jax.random.normal(keys[0], (B, S, H, P))
    b = jax.random.normal(keys[1], (B, S, G, N)) * 0.3
    c = jax.random.normal(keys[2], (B, S, G, N)) * 0.3
    dta = -jax.random.uniform(keys[3], (B, S, H), minval=0.01, maxval=0.3)
    dt = jax.random.uniform(keys[4], (B, S, H), minval=0.1, maxval=1.0)
    y, s = ops.ssd(x, b, c, dta, dt, chunk=chunk, use_kernel=True)
    y_ref, s_ref = ref.ssd_sequential_ref(x, b, c, dta, dt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=2e-3, atol=2e-3)


def test_ssd_decode_step_matches_sequential():
    """Running ssd_decode_step token-by-token == full-sequence oracle."""
    B, S, H, G, P, N = 1, 16, 2, 1, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (B, S, H, P))
    b = jax.random.normal(keys[1], (B, S, G, N)) * 0.3
    c = jax.random.normal(keys[2], (B, S, G, N)) * 0.3
    dta = -jax.random.uniform(keys[3], (B, S, H), minval=0.01, maxval=0.3)
    dt = jax.random.uniform(keys[4], (B, S, H), minval=0.1, maxval=1.0)
    y_ref, s_ref = ref.ssd_sequential_ref(x, b, c, dta, dt)

    state = jnp.zeros((B, H, N, P), jnp.float32)
    ys = []
    for t in range(S):
        state, y = ops.ssd_decode_step(
            state, x[:, t], b[:, t], c[:, t], dta[:, t], dt[:, t])
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_init_state_carries():
    """ops.ssd with init_state == running the two halves back to back."""
    B, S, H, G, P, N = 1, 32, 2, 1, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(12), 5)
    x = jax.random.normal(keys[0], (B, S, H, P))
    b = jax.random.normal(keys[1], (B, S, G, N)) * 0.3
    c = jax.random.normal(keys[2], (B, S, G, N)) * 0.3
    dta = -jax.random.uniform(keys[3], (B, S, H), minval=0.01, maxval=0.3)
    dt = jax.random.uniform(keys[4], (B, S, H), minval=0.1, maxval=1.0)
    y_full, s_full = ops.ssd(x, b, c, dta, dt, chunk=16)
    h = S // 2
    y1, s1 = ops.ssd(x[:, :h], b[:, :h], c[:, :h], dta[:, :h], dt[:, :h],
                     chunk=16)
    y2, s2 = ops.ssd(x[:, h:], b[:, h:], c[:, h:], dta[:, h:], dt[:, h:],
                     chunk=16, init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------- ops wrappers


def test_ops_dispatch_kernel_vs_ref_paths():
    x = jax.random.normal(jax.random.PRNGKey(13), (16, 32))
    w = jnp.ones((32,))
    a = ops.rmsnorm(x, w, use_kernel=True)
    b = ops.rmsnorm(x, w, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_ops_taskbench_nd_shapes():
    x = jax.random.uniform(jax.random.PRNGKey(14), (3, 5, 7))
    got = ops.taskbench_compute(x, 5)
    want = ref.taskbench_compute_ref(x, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
