"""Pallas fused-timestep megakernel for Task Bench graphs.

One ``pallas_call`` executes an ENTIRE Task Bench timestep — gather the
padded dependency slots from the previous-state buffer, combine them
(masked mean), and run the grain-size body — where the ``fused`` backend
emits one gather + one combine + one body op per step. At fine grain the
per-op dispatch cost of that chain is exactly the overhead the paper's METG
measures, so fusing the step control path lowers the repo's measurable
floor (cf. Task Bench SC'20 §6.1: sub-microsecond METG needs a fused
per-task path).

Batching contract: all operands carry a leading K axis — a
``GraphEnsemble``'s K members' combines and bodies batch into the SAME
launch (K is the slowest grid dimension, so member k's row-blocks are
contiguous program instances; see DESIGN.md §4 for why K is an operand axis
and not a vmap).

Inputs (see ``prepare_step_operands`` for how runtimes build idx/wgt):

  src  (K, S, payload)  previous-state rows to gather FROM. S may exceed the
                        output width W (halo-extended local blocks).
  idx  (K, W, D) int32  dependency slot -> src row. Every output row must
                        have >= 1 live slot: rows with no dependencies are
                        self-padded (idx = own row, weight 1), which encodes
                        task_kernels.combine_dependencies' "zero deps keep
                        own state" rule with no in-kernel branch.
  wgt  (K, W, D) f32    pre-normalized combine weights (mask / live-count),
                        so the masked MEAN is a single weighted sum — no
                        in-kernel max/divide/where.

Temporal blocking (``steps_per_launch=S > 1``): the classic deep-halo
stencil trick applied to the whole Task Bench step. Since every
halo-expressible pattern advances at most ``r`` rows of influence per step,
a source buffer extended by ``S*r`` rows per side holds enough remote state
for ``S`` consecutive timesteps — the kernel iterates combine + body ``S``
times on a fixed-size working buffer whose VALID region shrinks by ``r``
rows per inner step, and the caller slices the owned rows (still valid
after ``S`` shrinks) out of the result. One launch and one (deep) halo
exchange then serve ``S`` steps instead of one. Contract differences from
the single-step path:

  * square operands: src (K, M, payload), wgt (K, M, D) — every working row
    carries its OWN combine weights (indexed by its fixed global row id, so
    per-row edge clipping stays exact at every depth), and the output is
    the full (K, M, payload) buffer (caller slices the owned rows).
  * gather/onehot idx entries address the M-row working buffer itself.
  * gather/onehot tables may carry a leading depth axis — (K, S, M, D),
    one table per inner step — for patterns whose dependence sets change
    with t (butterfly strides, spread's rotation); depth d then combines
    with table d. Such launches run on an exactly-closed working buffer
    (the runtime's all-gather plan), so no valid-span shrink applies.
  * a per-depth activity mask ``act`` (K, S) freezes member k at inner step
    d when act[k, d] == 0 (heterogeneous-steps ensembles freeze at launch
    granularity; the final partial launch of any run is a masked tail).
  * the row grid collapses to 1 program per member: inner steps create
    cross-tile dependences, so the whole working buffer stays resident in
    VMEM for all S depths (kernels/schedule.py sizes S to the VMEM budget).

Pipeline phase split (``taskbench_step_interior`` / ``taskbench_step_boundary``):
the same blocked kernel invoked on two disjoint working buffers so the
runtime can overlap the next deep exchange with compute — the interior
entry runs on the owned block alone (its surviving rows touch no halo),
the boundary entry stacks both 3*depth-row edge buffers of all K members
onto the member axis of ONE launch and returns the rows the next exchange
sends. Both reuse the valid-span machinery unchanged; see DESIGN.md §6.

Loop-carried S=1 window (``taskbench_step_carry``): the runtime's scanned
S=1 halo loop keeps its state in the kernel's own tiled layout, owned rows
at a tile-aligned offset between two aligned halo blocks, so a step pads,
slices and concatenates nothing: the launch reads its window at that
offset and writes the new owned rows at the same offset, and only the
2*halo edge rows move between launches.

Three combine strategies, selected statically:

  window  for halo-expressible dependence patterns (the pallas_step
          runtime's default): slot j of wgt is the weight of the dependency
          at window offset j - halo, so the combine is a static unrolled
          sum of 2*halo+1 SHIFTED CONTIGUOUS SLICES of src — no gather at
          all, just VPU fused multiply-adds over (rows, payload) tiles.
          idx is ignored (src row = own row + j by construction).
  gather  dependency rows are fancy-indexed out of src (lax.gather) per
          the idx operand — the general path for arbitrary padded dep
          slots, in interpret mode only (a row gather does not lower on
          Mosaic).
  onehot  the combine is lifted to a (W, S) one-hot weight matrix applied
          with ``jnp.dot`` on the MXU — gather's form on the TPU.
  pair    for butterfly patterns (fft/tree): src carries [x | partner]
          halves stacked row-wise (S = 2*W; the runtime's stride plan
          builds the partner half with an XOR layout shuffle or a block
          permute), and the combine is elementwise (x + partner) * 0.5 —
          no gather, no index arithmetic, exact halving (every butterfly
          task has the two deps {p, p XOR 2^k}, so the masked mean IS
          (a + b) / 2 and * 0.5 reproduces it bit-for-bit). idx/wgt are
          ignored (wgt's row count still declares the output width W).

Validated bit-for-bit against ``ref.taskbench_step_ref`` (same value-level
body functions from ``bodies.py``) in interpret mode; see tests/test_kernels.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bodies import LANE, SUBLANE, apply_body

COMBINE_MODES = ("window", "gather", "onehot", "pair")

#: Combine weights are accumulated host-side in this dtype and rounded ONCE
#: to WEIGHT_DTYPE via finalize_weights — the single precision policy for
#: every operand builder (prepare_step_operands, the runtimes' window /
#: gather builders), so combine modes cannot drift in weight precision.
WEIGHT_ACCUM_DTYPE = np.float64
WEIGHT_DTYPE = np.float32


def finalize_weights(wgt: np.ndarray) -> np.ndarray:
    """Round host-accumulated combine weights once to the kernel dtype."""
    return np.asarray(wgt, WEIGHT_ACCUM_DTYPE).astype(WEIGHT_DTYPE)


def _step_kernel(
    src_ref,
    idx_ref,
    wgt_ref,
    o_ref,
    *,
    kind: str,
    iterations: int,
    scratch: int,
    payload: int,
    combine: str,
    block_rows: int,
    pair_rows: int = 0,
):
    wgt = wgt_ref[0]  # (Wb, D)
    n = wgt.shape[0]

    # window/pair read their rows straight from the ref at a dynamic row
    # offset (pl.ds): Mosaic lowers a ref load at any sublane offset, but
    # not a dynamic_slice of a loaded value (DESIGN.md §4)
    if combine == "pair":
        # src = [x | partner] halves (second half starts at the TRUE
        # unpadded width pair_rows): the combine is elementwise
        # (a + b) * 0.5 — gather-free, and exact halving keeps it
        # bit-identical to the 2-dep masked mean.
        row0 = pl.program_id(1) * block_rows
        a = src_ref[0, pl.ds(row0, n), :].astype(jnp.float32)
        b = src_ref[0, pl.ds(pair_rows + row0, n), :].astype(jnp.float32)
        x = (a + b) * jnp.float32(0.5)
    elif combine == "window":
        # wgt column j weighs the dependency at window offset j - halo:
        # out row w combines src rows [row0 + w .. row0 + w + 2*halo], a
        # static unrolled slice-FMA chain (no gather, no index arithmetic).
        row0 = pl.program_id(1) * block_rows
        x = jnp.zeros((n, src_ref.shape[-1]), jnp.float32)
        for j in range(wgt.shape[1]):
            win = src_ref[0, pl.ds(row0 + j, n), :].astype(jnp.float32)
            x = x + win * wgt[:, j:j + 1]
    elif combine == "gather":  # interpret mode only: no Mosaic row gather
        idx = idx_ref[0]
        gathered = src_ref[0][idx].astype(jnp.float32)  # (Wb, D, Pp)
        x = (gathered * wgt[..., None]).sum(axis=1)
    else:  # onehot: lift the gather to an MXU matmul
        src = src_ref[0]
        x = _onehot_dot(_onehot_matrix(idx_ref[0], wgt, src.shape[0]),
                        src.astype(jnp.float32))
    o_ref[0] = _apply_body_padded(
        x.astype(o_ref.dtype), kind=kind, iterations=iterations,
        scratch=scratch, payload=payload,
    )


def _onehot_matrix(idx, wgt, cols: int):
    """(rows, cols) combine matrix: row w holds wgt[w, j] at column
    idx[w, j] (duplicate slots add up), so ``C @ src`` is the weighted
    gather."""
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, cols), 2)
    return ((idx[..., None] == col).astype(jnp.float32)
            * wgt[..., None]).sum(axis=1)


def _onehot_dot(C, srcf):
    """``C @ srcf`` at full f32 precision: the MXU's default single
    bf16 pass would round the state and the 1/live-count weights."""
    return jnp.dot(C, srcf, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _apply_body_padded(x, *, kind, iterations, scratch, payload):
    """Body over a lane-padded (rows, Pp) tile, true-payload-aware.

    The memory_bound sweep mixes columns (roll), so it must see the TRUE
    payload slice; other bodies are columnwise and run on the padded tile.
    """
    if kind == "memory_bound" and iterations > 0:
        true = apply_body(x[:, :payload], kind, iterations, scratch)
        return jnp.pad(true, ((0, 0), (0, x.shape[-1] - payload)))
    return apply_body(x, kind, iterations, scratch)


def _blocked_step_kernel(
    src_ref,
    idx_ref,
    wgt_ref,
    act_ref,
    o_ref,
    *,
    kind: str,
    iterations: int,
    scratch: int,
    payload: int,
    combine: str,
    steps_per_launch: int,
    time_varying: bool = False,
):
    """S fused timesteps on one member's deep-halo-extended working buffer.

    The buffer keeps its full M rows at every depth; only the VALID span
    shrinks (by halo rows per side per step). Rows outside the valid span
    compute garbage from clamped windows / zero weights — harmless, because
    a row consumed at depth d+1 sits at least one halo inside the rows valid
    at depth d, and the caller only slices rows valid after all S depths.

    ``time_varying`` (gather/onehot only): idx/wgt carry a leading (S,)
    depth axis — one table per inner step — so patterns whose dependence
    sets change with t (butterfly strides, spread's rotation) can run
    blocked: depth d applies table d. The act-mask freezing is unchanged.

    Every index that varies with the depth ``d`` lands on a ref, never on
    a loaded value: the (K, S) act mask lives whole in SMEM (read as the
    scalar ``act_ref[k, d]``) and depth tables are indexed on their
    leading ref axis, the two forms Mosaic lowers (DESIGN.md §4).
    """
    k = pl.program_id(0)
    buf0 = src_ref[0]  # (Mp, Pp) working state, full size at every depth
    M = buf0.shape[0]
    if not time_varying:
        wgt = wgt_ref[0]  # (Mp, D) per-row weights, fixed across depths
        #                   (each row's global id never changes, so neither
        #                   do its edge-clipped combine weights)
        halo = (wgt.shape[1] - 1) // 2 if combine == "window" else 0
        if combine == "onehot":
            # idx/wgt are depth-invariant, so the (M, M) one-hot combine
            # matrix is built ONCE per launch, not once per inner step
            onehot_C = _onehot_matrix(idx_ref[0], wgt, M)

    def depth_step(d, buf):
        srcf = buf.astype(jnp.float32)
        if time_varying:
            # (S, Mp, D) tables: depth d combines with table d
            ti = idx_ref[0, d]
            tw = wgt_ref[0, d]
            if combine == "gather":
                x = (srcf[ti] * tw[..., None]).sum(axis=1)
            else:  # onehot, built per depth (the matrix changes with d)
                x = _onehot_dot(_onehot_matrix(ti, tw, M), srcf)
        elif combine == "window":
            # out row i combines work rows [i .. i + 2*halo] of the +-halo
            # zero-padded buffer: same static slice-FMA chain as the
            # single-step kernel, full-buffer width
            zpad = jnp.zeros((halo, srcf.shape[1]), jnp.float32)
            work = jnp.concatenate([zpad, srcf, zpad], axis=0)
            x = jnp.zeros((M, srcf.shape[1]), jnp.float32)
            for j in range(wgt.shape[1]):
                x = x + work[j:j + M] * wgt[:, j:j + 1]
        elif combine == "gather":
            idx = idx_ref[0]  # (Mp, D) absolute rows of THIS buffer
            gathered = srcf[idx]  # (Mp, D, Pp)
            x = (gathered * wgt[..., None]).sum(axis=1)
        else:  # onehot: lift the self-gather to an MXU matmul
            x = _onehot_dot(onehot_C, srcf)
        x = _apply_body_padded(
            x.astype(buf.dtype), kind=kind, iterations=iterations,
            scratch=scratch, payload=payload,
        )
        # masked freeze: inactive depths (a frozen ensemble member, or the
        # tail of the final partial launch) carry the buffer through intact
        return jnp.where(act_ref[k, d] > 0.5, x, buf)

    # ROLLED loop over depths (the buffer is full-size at every depth
    # precisely so the carry shape is loop-invariant): a rolled loop
    # materializes the buffer between depths, which keeps compile size
    # O(1) in S and stops XLA:CPU from fusing the whole depth chain into
    # one recompute cone (interpret mode would otherwise get slower per
    # step as S grows, inverting the launch-amortization win).
    o_ref[0] = jax.lax.fori_loop(0, steps_per_launch, depth_step, buf0)


def _blocked_call(src, idx, wgt, act, *, kind, iterations, scratch,
                  combine, interpret):
    """pallas_call for the temporal-blocked path: square (K, M, *) operands,
    one program per member (inner steps couple all rows, so no row grid).
    ``wgt.ndim == 4`` selects the time-varying contract: (K, S, M, D)
    idx/wgt tables, one per inner depth (gather/onehot only)."""
    K, M, payload = src.shape
    S = act.shape[1]
    if combine == "pair":
        raise ValueError(
            "pair combine is per-step only (blocked butterfly launches "
            "use gather/onehot with time-varying tables)")
    time_varying = wgt.ndim == 4
    if time_varying:
        if combine == "window":
            raise ValueError(
                "window combine has no time-varying form (halo patterns "
                "have period 1); use gather or onehot")
        if wgt.shape[:3] != (K, S, M):
            raise ValueError(
                f"time-varying tables must be (K, S, M, D) = ({K}, {S}, "
                f"{M}, ...), got {wgt.shape}")
        if idx.shape != wgt.shape:
            raise ValueError(
                f"operand shape mismatch: {idx.shape}/{wgt.shape}")
    else:
        if wgt.shape[:2] != (K, M):
            raise ValueError(
                f"blocked path needs square operands: src {src.shape} vs "
                f"wgt {wgt.shape} (every working row carries its own weights)"
            )
        if combine == "window":
            idx = jnp.zeros((K, 1, 1), jnp.int32)  # semantically unused
        elif idx.shape != wgt.shape:
            raise ValueError(f"operand shape mismatch: {idx.shape}/{wgt.shape}")
    D = wgt.shape[-1]
    if act.shape[0] != K:
        raise ValueError(f"act must be (K, S), got {act.shape} for K={K}")

    lane, sublane = (1, 1) if interpret else (LANE, SUBLANE)
    pad_p = (-payload) % lane
    pad_m = (-M) % sublane
    row_axis = 2 if time_varying else 1
    tab_pad = [(0, 0)] * wgt.ndim
    tab_pad[row_axis] = (0, pad_m)
    with jax.named_scope("lane_pad"):
        srcp = jnp.pad(src, ((0, 0), (0, pad_m), (0, pad_p)))
        idxp = idx if combine == "window" else jnp.pad(idx, tab_pad)
        wgtp = jnp.pad(wgt, tab_pad)
    Mp, Pp = srcp.shape[1], srcp.shape[2]
    if combine == "window":
        idx_block = pl.BlockSpec((1, 1, 1), lambda k: (k, 0, 0))
    elif time_varying:
        idx_block = pl.BlockSpec((1, S, Mp, D), lambda k: (k, 0, 0, 0))
    else:
        idx_block = pl.BlockSpec((1, Mp, D), lambda k: (k, 0, 0))
    wgt_block = (
        pl.BlockSpec((1, S, Mp, D), lambda k: (k, 0, 0, 0))
        if time_varying
        else pl.BlockSpec((1, Mp, D), lambda k: (k, 0, 0))
    )

    out = pl.pallas_call(
        functools.partial(
            _blocked_step_kernel,
            kind=kind,
            iterations=iterations,
            scratch=scratch,
            payload=payload,
            combine=combine,
            steps_per_launch=S,
            time_varying=time_varying,
        ),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, Mp, Pp), lambda k: (k, 0, 0)),
            idx_block,
            wgt_block,
            # whole (K, S) mask as SMEM scalars: a (1, S) VMEM block of it
            # breaks the (8, 128) tiling rule, and a depth-indexed read of
            # a vector value does not lower
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, Mp, Pp), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, Mp, Pp), src.dtype),
        interpret=interpret,
        name="taskbench_step_blocked",
    )(srcp, idxp, wgtp, act)
    with jax.named_scope("lane_slice"):
        return out[:, :M, :payload]


@functools.partial(
    jax.jit,
    static_argnames=(
        "kind", "iterations", "scratch", "block_rows", "combine",
        "steps_per_launch", "interpret", "_tile",
    ),
)
def taskbench_step_pallas(
    src: jax.Array,
    idx: jax.Array,
    wgt: jax.Array,
    act: jax.Array | None = None,
    *,
    kind: str = "compute_bound",
    iterations: int = 16,
    scratch: int = 2048,
    block_rows: int = 0,
    combine: str = "gather",
    steps_per_launch: int = 1,
    interpret: bool = False,
    _tile: Tuple[int, int] | None = None,
) -> jax.Array:
    """Fused Task Bench timestep(s) for K graphs.

    ``steps_per_launch=1`` (default): one timestep, (K, W, payload) out.
    Under ``combine="window"`` src is the halo-extended block, owned row w
    at src row w + halo; the operands are padded to the chip's tiles and
    the output sliced back on every call. The runtime's scanned S=1 halo
    loop does not use this form: it keeps its state in the tiled layout
    and launches ``taskbench_step_carry`` on it; the per-step callers
    (tuple and host-stepped ensembles, the row grid, gather/onehot, the
    stride plan's pair combine) do.
    ``block_rows=0`` keeps each member's full width in one program (the
    fine-grain default — minimal grid overhead); set it to tile wide graphs
    so the (block_rows, payload) working set fits VMEM. The private
    ``_tile`` (sublanes, lanes) overrides the padding tile, the chip's
    (8, 128) or (1, 1) in interpret mode, so tests can run the chip's
    layout in interpret mode.

    ``steps_per_launch=S > 1``: the temporal-blocked path (see module
    docstring) — square (K, M, *) operands on a deep-halo working buffer,
    a required (K, S) ``act`` mask, full (K, M, payload) buffer out
    (caller slices the rows still valid after S halo shrinks);
    ``block_rows`` is ignored (one program per member).
    """
    if combine not in COMBINE_MODES:
        raise ValueError(f"unknown combine mode {combine!r}; known {COMBINE_MODES}")
    if combine == "gather" and not interpret:
        # a row gather (src[idx] on a value) has no Mosaic lowering
        raise ValueError(
            "combine='gather' does not lower for the TPU (Mosaic has no row "
            "gather); use combine='onehot', the MXU form of the same combine")
    if src.ndim != 3 or wgt.ndim not in (3, 4):
        raise ValueError(
            f"expected (K, S, payload)/(K, W, D) operands, got "
            f"{src.shape}/{wgt.shape}"
        )
    if wgt.ndim == 4 and steps_per_launch <= 1:
        raise ValueError(
            "time-varying (K, S, M, D) tables require steps_per_launch > 1")
    if steps_per_launch < 1:
        raise ValueError(f"steps_per_launch must be >= 1, got {steps_per_launch}")
    if steps_per_launch > 1:
        if act is None:
            raise ValueError("steps_per_launch > 1 requires an act mask")
        if act.ndim != 2 or act.shape[1] != steps_per_launch:
            raise ValueError(
                f"act must be (K, {steps_per_launch}), got {act.shape}")
        return _blocked_call(
            src, idx, wgt, act.astype(jnp.float32), kind=kind,
            iterations=iterations, scratch=scratch, combine=combine,
            interpret=interpret,
        )
    K, S, payload = src.shape
    _, W, D = wgt.shape
    if wgt.shape[0] != K:
        raise ValueError(f"operand K mismatch: {src.shape}/{wgt.shape}")
    if combine == "pair" and S != 2 * W:
        raise ValueError(
            f"pair combine needs src rows == 2 * W (the [x | partner] "
            f"halves), got {S} vs W = {W}")
    if combine in ("window", "pair"):
        # idx is semantically unused (window: src row = own row + slot
        # offset; pair: src row = own row and own row + W); feed a
        # 1-element dummy so no dead (K, W, D) block is DMA'd per program
        idx = jnp.zeros((K, 1, 1), jnp.int32)
    elif idx.shape != wgt.shape:
        raise ValueError(f"operand shape mismatch: {idx.shape}/{wgt.shape}")

    # Hardware tiles: payload -> 128-lane multiple, rows -> sublane/block
    # multiples. Padded idx rows gather src row 0 at weight 0, padded src
    # rows are never indexed, padded payload columns stay zero through the
    # (row-wise linear) combine; everything is sliced off on return. The
    # interpreter has no tile constraints, so off-TPU the operands stay
    # unpadded — lane-padding there would double the per-step elementwise
    # work this kernel exists to minimize.
    sublane, lane = _tile or ((1, 1) if interpret else (SUBLANE, LANE))
    pad_p = (-payload) % lane
    block_rows = block_rows or W + (-W) % sublane
    block_rows = max(sublane, min(block_rows, W + (-W) % sublane))
    pad_w = (-W) % block_rows
    if combine == "window":
        # out row w reads src rows [w .. w + D-1]: padded out rows must
        # still slice in bounds (their weights are zero, values discarded)
        if S < W + D - 1:
            raise ValueError(
                f"window combine needs src rows >= W + D - 1 = {W + D - 1}, "
                f"got {S} (window D = {D} includes the halo)"
            )
        pad_s = max(pad_w, (-S) % sublane)
    elif combine == "pair":
        # padded out rows slice src rows up to W + Wp: keep pad_s >= pad_w
        pad_s = max(pad_w, (-S) % sublane)
    else:
        pad_s = (-S) % sublane
    with jax.named_scope("lane_pad"):
        srcp = jnp.pad(src, ((0, 0), (0, pad_s), (0, pad_p)))
        idxp = (idx if combine in ("window", "pair")
                else jnp.pad(idx, ((0, 0), (0, pad_w), (0, 0))))
        wgtp = jnp.pad(wgt, ((0, 0), (0, pad_w), (0, 0)))
    Sp, Pp = srcp.shape[1], srcp.shape[2]
    Wp = W + pad_w
    idx_block = (
        pl.BlockSpec((1, 1, 1), lambda k, i: (k, 0, 0))
        if combine in ("window", "pair")
        else pl.BlockSpec((1, block_rows, D), lambda k, i: (k, i, 0))
    )

    out = pl.pallas_call(
        functools.partial(
            _step_kernel,
            kind=kind,
            iterations=iterations,
            scratch=scratch,
            payload=payload,
            combine=combine,
            block_rows=block_rows,
            pair_rows=W,
        ),
        grid=(K, Wp // block_rows),
        in_specs=[
            pl.BlockSpec((1, Sp, Pp), lambda k, i: (k, 0, 0)),
            idx_block,
            pl.BlockSpec((1, block_rows, D), lambda k, i: (k, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_rows, Pp), lambda k, i: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, Wp, Pp), src.dtype),
        interpret=interpret,
        name="taskbench_step_s1",
    )(srcp, idxp, wgtp)
    with jax.named_scope("lane_slice"):
        return out[:, :W, :payload]


def _window_carry_kernel(src_ref, wgt_ref, o_ref, *, kind, iterations,
                         scratch, payload, offset):
    """One S=1 window step on a tiled, halo-extended carry.

    Owned row w sits at carry row ``offset + w`` and combines carry rows
    ``[offset + w - halo .. offset + w + halo]``, each window a ref load
    (any sublane offset lowers). The new owned rows land at the same
    tile-aligned offset; the halo blocks pass through unchanged (the
    caller rewrites their edge rows).
    """
    wgt = wgt_ref[0]  # (n, D): owned rows tile-padded, pad rows weigh 0
    n, taps = wgt.shape
    base = offset - (taps - 1) // 2
    x = jnp.zeros((n, src_ref.shape[-1]), jnp.float32)
    for j in range(taps):
        win = src_ref[0, pl.ds(base + j, n), :].astype(jnp.float32)
        x = x + win * wgt[:, j:j + 1]
    new = _apply_body_padded(
        x.astype(o_ref.dtype), kind=kind, iterations=iterations,
        scratch=scratch, payload=payload,
    )
    if offset:
        o_ref[0, :offset, :] = src_ref[0, :offset, :]
        o_ref[0, offset + n:, :] = src_ref[0, offset + n:, :]
    o_ref[0, offset:offset + n, :] = new


@functools.partial(
    jax.jit,
    static_argnames=("offset", "payload", "kind", "iterations", "scratch",
                     "interpret"),
)
def taskbench_step_carry(
    carry: jax.Array,
    wgt: jax.Array,
    *,
    offset: int,
    payload: int,
    kind: str = "compute_bound",
    iterations: int = 16,
    scratch: int = 2048,
    interpret: bool = False,
) -> jax.Array:
    """One S=1 window timestep on a loop-carried, halo-extended state (the
    runtime's S=1 halo loop; DESIGN.md §4).

    ``carry`` (K, M, Pp) holds each member's owned rows at rows
    ``[offset, offset + n)`` with ``halo = (D - 1) // 2`` neighbour rows
    directly on each side; ``wgt`` (K, n, D) is the window weight table of
    the owned rows, zero on rows past the true block. The caller chooses
    the tiling: on the chip ``offset`` and ``n`` are multiples of 8 and Pp
    of 128, so nothing here pads or slices, and ``payload`` is the true
    width inside Pp (the memory body sweeps only that). Returns a new
    carry of the same shape: the new owned rows at the same offset, the
    rest passed through. The output does not alias ``carry``: on a v5e an
    aliased launch took 1.4 µs longer at W = 4096, more than the
    whole-state loop copy it saves, and a loop that runs two launches an
    iteration needs neither (DESIGN.md §4).
    """
    if carry.ndim != 3 or wgt.ndim != 3 or wgt.shape[0] != carry.shape[0]:
        raise ValueError(
            f"expected (K, M, Pp)/(K, n, D) operands, got "
            f"{carry.shape}/{wgt.shape}")
    K, M, Pp = carry.shape
    _, n, taps = wgt.shape
    halo = (taps - 1) // 2
    if taps % 2 != 1 or offset < halo or M < offset + n + halo:
        raise ValueError(
            f"carry rows {M} cannot hold {n} owned rows at offset {offset} "
            f"with {halo} halo rows each side (window D = {taps})")
    if not 0 < payload <= Pp:
        raise ValueError(f"payload {payload} outside the carry's {Pp} lanes")
    return pl.pallas_call(
        functools.partial(
            _window_carry_kernel, kind=kind, iterations=iterations,
            scratch=scratch, payload=payload, offset=offset,
        ),
        grid=(K,),
        in_specs=[
            pl.BlockSpec((1, M, Pp), lambda k: (k, 0, 0)),
            pl.BlockSpec((1, n, taps), lambda k: (k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, M, Pp), lambda k: (k, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(carry.shape, carry.dtype),
        interpret=interpret,
        name="taskbench_step_s1",
    )(carry, wgt)


def taskbench_step_interior(
    src: jax.Array,
    idx: jax.Array,
    wgt: jax.Array,
    act: jax.Array,
    *,
    depth: int,
    **kw,
) -> jax.Array:
    """Interior phase of a software-pipelined blocked launch.

    The working buffer is the OWNED (K, B, payload) block alone — no halo
    rows at all. The valid span still shrinks by ``r`` rows per side per
    inner step (the standard blocked contract), so after S steps exactly the
    rows whose S-step light cone never left the block survive: [depth,
    B - depth) with ``depth = S*r``. Those rows are what this entry point
    returns, and by construction they depend on no in-flight halo — the
    property the pipelined runtime exploits to run this launch UNDER the
    next exchange. Requires ``B > 2*depth`` (a nonempty interior); operands
    are per-row tables for the owned rows (wgt (K, B, D)).
    """
    B = src.shape[1]
    if B <= 2 * depth:
        raise ValueError(
            f"interior phase needs block > 2*depth, got {B} <= {2 * depth}")
    out = taskbench_step_pallas(src, idx, wgt, act, **kw)
    return jax.lax.slice_in_dim(out, depth, B - depth, axis=1)


def taskbench_step_boundary(
    left: jax.Array,
    right: jax.Array,
    idx: jax.Array,
    wgt: jax.Array,
    act: jax.Array,
    *,
    depth: int,
    **kw,
) -> Tuple[jax.Array, jax.Array]:
    """Boundary phase of a software-pipelined blocked launch.

    ``left``/``right`` are the two (K, 3*depth, payload) edge working
    buffers — [received halo | first 2*depth owned rows] and [last 2*depth
    owned rows | received halo] — fused ROW-WISE into one (K, 6*depth)
    working buffer so both sides of all K members ride ONE program instance
    per member. The fusion is exact: the left side's surviving rows are
    buffer rows [depth, 2*depth) whose S-step light cone spans buffer rows
    [0, 3*depth - 1], the right side's are rows [4*depth, 5*depth) with
    cone [3*depth, 6*depth - 1] — neither cone crosses the junction at row
    3*depth, so the halves cannot contaminate each other (junction-adjacent
    rows DO mix across it at depth >= 1, but those are garbage rows outside
    both cones). Each side's middle ``depth`` rows are the new edge rows of
    the block — precisely the rows the NEXT launch's exchange must send,
    which is why the pipelined runtime issues that exchange on this entry
    point's outputs. idx/wgt follow the fused buffer layout (rows
    [left..., right...] on the row axis); ``act`` is the member mask
    (K, S), shared by both sides. Returns (left_out, right_out), each
    (K, depth, payload).
    """
    if left.shape != right.shape or left.shape[1] != 3 * depth:
        raise ValueError(
            f"boundary buffers must both be (K, {3 * depth}, payload), got "
            f"{left.shape}/{right.shape}")
    src = jnp.concatenate([left, right], axis=1)
    out = taskbench_step_pallas(src, idx, wgt, act, **kw)
    return (jax.lax.slice_in_dim(out, depth, 2 * depth, axis=1),
            jax.lax.slice_in_dim(out, 4 * depth, 5 * depth, axis=1))


def prepare_step_operands(dep_lists, width: int, self_pos) -> tuple:
    """Host-side build of one member's (idx, wgt) kernel operands.

    Args:
      dep_lists: length-``width`` list; entry p is the sequence of SRC ROW
        positions task p gathers (duplicates allowed — they weigh double,
        matching combine_dependencies). Empty -> self-padded.
      width: number of output rows W.
      self_pos: length-``width`` array of each row's own position in src
        (the zero-dep "keep own state" row).

    Returns:
      idx int32 (W, D), wgt WEIGHT_DTYPE (W, D) with D = max(1, max deps);
      weights pre-normalized to 1/live-count (accumulated in
      WEIGHT_ACCUM_DTYPE, rounded once by finalize_weights — the shared
      precision policy) so the kernel's weighted sum IS the masked mean.
    """
    D = max(1, max((len(d) for d in dep_lists), default=0))
    idx = np.zeros((width, D), dtype=np.int32)
    wgt = np.zeros((width, D), dtype=WEIGHT_ACCUM_DTYPE)
    for p, deps in enumerate(dep_lists):
        if not deps:
            idx[p, 0] = self_pos[p]
            wgt[p, 0] = 1.0
            continue
        w = 1.0 / len(deps)
        for j, q in enumerate(deps):
            idx[p, j] = q
            wgt[p, j] = w
    return idx, finalize_weights(wgt)
