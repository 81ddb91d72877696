"""`pallas_step` runtime — fused megakernel launches, temporally blockable.

The sixth rung of the backend ladder: like `bsp_scan` the whole timestep
loop lives in one jit (shard_map over devices, lax.scan over launches), but
where every other backend emits one gather + one combine + one body op per
dependency slot per step, this backend lowers the ENTIRE step — gather the
padded dependency slots from the previous-state buffer, masked-mean
combine, grain-size body — into a single `pallas_call`
(repro.kernels.taskbench_step). At fine grain the other backends' floor
measures XLA op-dispatch overhead; this one's floor is the kernel itself,
which is the fused per-task control path Task Bench (SC'20) shows is needed
for sub-microsecond METG.

Temporal blocking (``steps_per_launch=S``): after PR 2 the remaining
per-step cost was one kernel launch plus one ring halo exchange PER STEP.
Since every halo-expressible pattern advances at most ``r`` rows of
influence per step, exchanging a deep halo of ``S*r`` rows once lets each
device advance S full timesteps locally before communicating again — the
classic deep-halo stencil optimization applied to the whole Task Bench
step. The loop becomes ``ceil((T-1)/S)`` launches; each launch's kernel
iterates combine + body S times on a working buffer whose valid region
shrinks by ``r`` rows per inner step (kernels/taskbench_step.py has the
kernel-side contract). Per-row combine weights ride along: they are
indexed by fixed global row id, so ONE deep exchange of the weight (and,
for gather/onehot, relative-offset) tables before the scan gives every
working row its exact edge-clipped weights at every depth. Heterogeneous
``steps`` freeze at launch granularity through a per-depth activity mask
baked host-side into the scan inputs — the final partial launch of any run
is the same mask (the "masked tail"). ``steps_per_launch`` accepts an int,
``"auto"`` (VMEM-budget tuner, kernels/schedule.py), and defaults to 1
(the PR-2 per-step behavior).

Dataflow: points are block-distributed like `bsp`; halo-expressible
patterns exchange ``S*r`` edge rows per ring direction
(`_halo.exchange_halos`, multi-hop when the depth exceeds a block), and the
megakernel gathers from the halo-EXTENDED local block through
host-precomputed (idx, wgt) operands — weights pre-normalized to
1/live-count and zero-dep rows self-padded, so the kernel has no
edge/wrap/empty branches.

Ensembles: a stackable ensemble with a uniform KernelSpec runs ALL K
members' combines and bodies in the SAME launch (the megakernel's leading K
axis); one deep ring exchange moves every member's halos for S steps at
once. The halo plan has one builder for this, `_build_halo_stacked`, and a
single graph is its one-member case; each launch form (`_window_carry_run`,
`_extend_launch`, `_serial_launch`, `_pipelined_launch`) is written once at
module level and shared with the host-stepped and tuple paths. Mixed-spec or
ragged-shape ensembles fall back to one launch per member inside the same
jitted scan.

Double-buffered deep-halo pipeline (``pipeline=True``, the default): with
blocking alone every deep exchange still sits serially between launches, so
at fine grain the wall/step floor measures ring latency. The pipelined
schedule splits each blocked launch into a boundary phase (the 2*S*r edge
rows whose S-step light cone touches the incoming halo) and an interior
phase (everything else), and issues the NEXT launch's exchange on the
boundary outputs — which are exactly the rows the neighbors need — before
running the interior, so in steady state the exchange of launch l+1 is in
flight under the interior compute of launch l (`_halo.exchange_edges_start`
/ the HaloHandle carried in the scan are the double-buffered halo slots).
``pipeline=False`` is the serial-exchange ablation, mirroring the overlap
runtime's ``overlap=False``; blocks with no interior (B <= 2*S*r, where
splitting buys nothing and costs a second launch) fall back to it
automatically. The scan's final iteration issues one dead exchange (uniform
bodies); its cost is 1/L of the exchanges and it keeps the loop rolled.

Beyond halos — the pattern→plan dispatch (DESIGN.md §7): non-local
dependence patterns have no bounded per-step reach, so ``supports`` routes
every graph to one of three PLANS instead of refusing anything non-halo:

  halo       halo-expressible period-1 patterns — everything above.
  stride     butterfly patterns (fft/tree). Step t pairs p with
             p XOR 2^(t-1 mod log2 W): in-block strides materialize the
             partner rows with an XOR layout shuffle (reshape + pair
             swap, no gather), block strides with one XOR collective
             permute (`_halo.exchange_stride_start/join`) delivering the
             partner block; the megakernel then combines the stacked
             [x | partner] halves with the gather-free "pair" mode —
             elementwise (a+b)*0.5, bit-identical to the fused oracle
             (gather/onehot stay selectable as ablations). One launch +
             at most one collective per step (a single graph runs its
             period as straight-line levels); per-step by construction
             (temporal blocking a stride plan needs the XOR-subgroup
             closure of the launch window, which is the full gather — so
             EXPLICITLY blocked requests route to:)
  allgather  global patterns (spread, all_to_all) and blocked butterfly,
             for widths <= ``gather_width_cap``: one full-state gather
             per launch (`_halo.gather_global`), every gathered row
             advances exactly (no valid-span shrink), and TIME-VARYING
             (S, W, D) idx/wgt tables — butterfly slots selected per
             depth, spread's rotation computed in-scan — drive the
             onehot combine at each depth. Blocking trades replicated
             compute for 1/S the collectives; kernels/schedule.py's
             ``gathered_pays_off`` gates "auto".

Options: combine="window"|"gather"|"onehot" (see taskbench_step.py; the
non-halo plans cannot window — the default resolves to "pair" on the
stride plan and, on the allgather plan, to "gather" off-TPU / "onehot"
on TPU, with explicit "gather"/"onehot" honored as ablations — see
``_plan_combine``), steps_per_launch=int|"auto", pipeline=True|False,
block_rows, unroll, gather_width_cap=int, halo_impl="xla"|"ppermute".
"""
from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import patterns as _patterns
from repro.core.graph import GraphEnsemble, TaskGraph
from repro.core.runtimes import _halo
from repro.core.runtimes.base import EnsembleLaunchPlan, register
from repro.core.runtimes.bsp import AXIS, _BspBase
from repro.core.task_kernels import KernelSpec
from repro.kernels import ops as _kops
from repro.kernels import probes as _probes
from repro.kernels import schedule as _schedule
from repro.kernels.bodies import LANE, SUBLANE
from repro.kernels.taskbench_step import (
    WEIGHT_ACCUM_DTYPE,
    WEIGHT_DTYPE,
    finalize_weights,
    prepare_step_operands,
)
from repro.launch.mesh import make_row_member_mesh
from repro.obs import layer_span

#: Execution-plan kinds the pattern→plan dispatch resolves to.
PLAN_HALO = "halo"
PLAN_STRIDE = "stride"
PLAN_ALLGATHER = "allgather"
PLAN_KINDS = (PLAN_HALO, PLAN_STRIDE, PLAN_ALLGATHER)

#: Second mesh axis of the 2D (row, member) mesh: stacked ensembles shard
#: K members along it (``member_shards`` option), while every halo /
#: stride / gather transport keeps running over AXIS — in a 2D mesh a
#: named-axis collective only spans its own axis, so row transports
#: never cross the member axis by construction (DESIGN.md §12).
MEMBER_AXIS = "member"


def _ext_dep_operands(
    graph: TaskGraph, block: int, halo: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(W, D) idx/wgt into the halo-extended local block, for one timestep.

    Local row i of a block starting at global row p0 gathers from an
    extended buffer ext = [p0-halo .. p0+B-1+halo] (mod W, via ring
    exchange), so dependency q of global row p maps to extended position
    (p mod B) + halo + o where o is q's signed window offset from p. All
    halo-expressible patterns have period 1, so ONE slice serves every
    timestep t >= 1.
    """
    r = _patterns.halo_radius(graph)
    if r < 0:
        raise ValueError(f"{graph.pattern} is not halo-expressible")
    if graph.period != 1:
        raise ValueError(f"halo pattern {graph.pattern} must have period 1")
    W = graph.width

    def to_ext(p: int, q: int) -> int:
        for o in range(-r, r + 1):
            if (p + o) % W == q:
                return p % block + halo + o
        raise ValueError(f"dep {q} of point {p} outside halo radius {r}")

    ext_lists: List[List[int]] = [
        [to_ext(p, q) for q in graph.dependencies(1, p)] for p in range(W)
    ]
    selfs = [p % block + halo for p in range(W)]
    return prepare_step_operands(ext_lists, W, selfs)


def _rel_dep_operands(graph: TaskGraph) -> Tuple[np.ndarray, np.ndarray]:
    """(W, D) SIGNED-offset operands for the temporal-blocked gather modes.

    Row p's dependency q is stored as its window offset o (q == (p+o) mod
    W), not an absolute buffer position: offsets are a property of the
    global row alone, so the runtime can deep-halo-exchange these tables
    like state and convert to absolute working-buffer rows with a single
    ``+ arange(M)`` — every extended row then gathers its own dependencies
    at any launch depth. Zero-dep rows self-pad at offset 0.
    """
    r = _patterns.halo_radius(graph)
    if r < 0 or graph.period != 1:
        raise ValueError(f"{graph.pattern} is not halo-expressible")
    W = graph.width
    rel_lists: List[List[int]] = []
    for p in range(W):
        offs: List[int] = []
        for q in graph.dependencies(1, p):
            for o in range(-r, r + 1):
                if (p + o) % W == q:
                    offs.append(o)
                    break
            else:
                raise ValueError(f"dep {q} of point {p} outside halo {r}")
        rel_lists.append(offs)
    return prepare_step_operands(rel_lists, W, [0] * W)


def _self_operands(width: int, block: int) -> Tuple[np.ndarray, np.ndarray]:
    """(W, 1) identity operands (t=0: body only, src = raw local block)."""
    selfs = [p % block for p in range(width)]
    return prepare_step_operands([[] for _ in range(width)], width, selfs)


def _window_operands(
    graph: TaskGraph, halo: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(W, 2*halo+1) per-offset combine weights for the window kernel mode.

    Column halo + o carries the (pre-normalized) weight of the dependency
    at window offset o, so the kernel's combine is a static chain of
    shifted-slice FMAs — no gather. Edge clipping (stencil_1d, dom), the
    per-row keep set (random_nearest), duplicate window wraps (nearest
    with W <= 2r), and the zero-dep self-keep rule are all encoded in the
    weights; idx is unused in this mode (returned as zeros). Weights are
    per GLOBAL row and patterns have period 1, so the same row's weights
    are correct at every timestep — the property the temporal-blocked path
    relies on when it exchanges these tables as deep halos.
    """
    r = _patterns.halo_radius(graph)
    if r < 0 or graph.period != 1:
        raise ValueError(f"{graph.pattern} is not window-expressible")
    W = graph.width
    D = 2 * halo + 1
    # idx is unused in window mode (the kernel substitutes a 1-element
    # dummy); a single column keeps the shard_map row-sharding contract
    # without shipping a dead (W, D) block
    idx = np.zeros((W, 1), dtype=np.int32)
    wgt = np.zeros((W, D), dtype=WEIGHT_ACCUM_DTYPE)
    for p in range(W):
        deps = graph.dependencies(1, p)
        if not deps:
            wgt[p, halo] = 1.0  # zero deps: keep own state (self weight 1)
            continue
        share = 1.0 / len(deps)
        for q in deps:
            for o in range(-r, r + 1):
                if (p + o) % W == q:
                    wgt[p, halo + o] += share
                    break
            else:
                raise ValueError(f"dep {q} of point {p} outside halo {r}")
    return idx, finalize_weights(wgt)


def _stride_slot_tables(
    block: int, stride: int
) -> Tuple[np.ndarray, np.ndarray, bool]:
    """(B, 2) idx/wgt tables for one butterfly period slot (the
    gather/onehot ablations of the stride plan; the default pair combine
    needs no tables).

    Power-of-two width (graph-validated) means every point has exactly the
    two dependencies {p, p XOR stride} at weight 1/2 — a power of two, so
    0.5*a + 0.5*b is bit-identical to the fused oracle's (a + b) / 2
    under every combine. In-block strides (stride < block, which implies
    the partner shares the block since blocks are power-of-two sized)
    address the local rows; block strides address a [local | partner]
    working buffer (partner block at rows [B, 2B)). Returns
    (idx, wgt, off_block)."""
    i = np.arange(block, dtype=np.int32)
    off_block = stride >= block
    partner = (block + i) if off_block else (i ^ stride)
    idx = np.stack([i, partner], axis=1).astype(np.int32)
    wgt = np.full((block, 2), 0.5, dtype=WEIGHT_ACCUM_DTYPE)
    return idx, finalize_weights(wgt), off_block


def _global_slot_operands(graph: TaskGraph) -> Tuple[np.ndarray, np.ndarray]:
    """(period, W, D) idx + pre-normalized wgt tables in GLOBAL row ids.

    The all-gather plan's working buffer is the full state in global
    order, so the graph's own dependency arrays ARE the gather tables —
    no rebasing, any pattern. Weights follow the shared precision policy
    (mask / live-count accumulated wide, rounded once); zero-dep rows
    self-gather at weight 1 (combine_dependencies' keep-own-state rule).
    """
    idx, mask = graph.dependency_arrays()
    acc = np.asarray(mask, WEIGHT_ACCUM_DTYPE)
    live = acc.sum(-1, keepdims=True)
    wgt = acc / np.maximum(live, 1.0)
    zero = live[..., 0] == 0  # (period, W)
    if zero.any():
        P, W, _ = idx.shape
        idx = idx.copy()
        selfs = np.broadcast_to(np.arange(W, dtype=np.int32), (P, W))
        idx[..., 0] = np.where(zero, selfs, idx[..., 0])
        wgt[..., 0] = np.where(zero, 1.0, wgt[..., 0])
    return idx, finalize_weights(wgt)


def _spread_base_operands(graph: TaskGraph) -> Tuple[np.ndarray, np.ndarray]:
    """(W, D) t=1 tables for spread; timestep t rotates idx by +(t-1) mod W.

    spread's dependence set {(p + i*stride + (t-1)) mod W} shifts RIGIDLY
    with t, so one base table plus an in-scan additive rotation replaces
    the period-W stack ``_global_slot_operands`` would materialize. The
    live count |{i*stride mod W}| is point- and time-invariant, so the
    weight table never rotates."""
    W = graph.width
    lists = [graph.dependencies(1, p) for p in range(W)]
    D = max(1, max(len(l) for l in lists))
    idx = np.zeros((W, D), dtype=np.int32)
    acc = np.zeros((W, D), dtype=WEIGHT_ACCUM_DTYPE)
    for p, deps in enumerate(lists):
        share = 1.0 / len(deps)
        for j, q in enumerate(deps):
            idx[p, j] = q
            acc[p, j] = share
    return idx, finalize_weights(acc)


def _self_tables(block: int) -> Tuple[jax.Array, jax.Array]:
    """(B, 1) per-device identity tables for the t=0 body-only launch
    (device-invariant, so closures can carry them into shard_map)."""
    return (jnp.arange(block, dtype=jnp.int32)[:, None],
            jnp.ones((block, 1), WEIGHT_DTYPE))


def _xor_swap(x: jax.Array, stride: int) -> jax.Array:
    """Rows permuted by i -> i XOR stride (a power of two dividing the
    row count): reshape to (pairs, 2, stride, ...) and swap the pair axis
    — a pure layout shuffle, no gather. This is what makes the stride
    plan's in-block butterfly combine gather-free."""
    B = x.shape[0]
    g = x.reshape(B // (2 * stride), 2, stride, *x.shape[1:])
    return jnp.flip(g, axis=1).reshape(x.shape)


def _extend_state(s: jax.Array, depth: int, num_devices: int,
                  *, row_axis: int = 0) -> jax.Array:
    """Halo-extend a local block by ``depth`` rows per side (ring exchange;
    multi-hop past the block). Identity at depth 0."""
    if depth == 0:
        return s
    return _halo.ring_extend(s, depth, num_devices, AXIS, row_axis=row_axis)


class _CarryLayout(NamedTuple):
    """Rows and lanes of the S=1 window loop's carry, per member: owned
    rows ``[offset, offset + rows)`` between two ``offset``-row halo
    blocks, the ``halo`` rows next to the owned ones holding the ring
    neighbours' edges. ``offset`` and ``rows_p`` are whole sublane tiles
    and ``lanes`` whole lane tiles, so a step touches the full state only
    inside the megakernel."""

    halo: int
    offset: int
    rows: int
    rows_p: int
    lanes: int


def _carry_layout(rows: int, halo: int, payload: int,
                  tile: Optional[Tuple[int, int]] = None) -> _CarryLayout:
    """The carry for ``rows`` owned rows; ``tile`` (sublanes, lanes)
    defaults to the chip's (8, 128), and to (1, 1) in interpret mode."""
    if tile is None:
        tile = (1, 1) if _kops._interpret() else (SUBLANE, LANE)
    sub, lane = tile
    return _CarryLayout(halo=halo, offset=-(-halo // sub) * sub, rows=rows,
                        rows_p=-(-rows // sub) * sub,
                        lanes=-(-payload // lane) * lane)


def _carry_halos(carry: jax.Array, lay: _CarryLayout,
                 num_devices: int) -> jax.Array:
    """Refresh the (K, M, Pp) carry's halo rows in place: the ring
    exchange of the owned block's ``halo`` edge rows each way, written
    next to the owned rows. Identity at halo 0."""
    if lay.halo == 0:
        return carry
    with jax.named_scope("halo_update"):
        owned = jax.lax.slice_in_dim(carry, lay.offset,
                                     lay.offset + lay.rows, axis=1)
        rl, rr = _halo.exchange_halos(owned, lay.halo, num_devices, AXIS,
                                      row_axis=1)
        carry = jax.lax.dynamic_update_slice_in_dim(
            carry, rl, lay.offset - lay.halo, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            carry, rr, lay.offset + lay.rows, axis=1)


def _window_carry_run(local: jax.Array, w: jax.Array, w0: jax.Array, *,
                      lay: _CarryLayout, steps: int, num_devices: int,
                      kw: dict, unroll: int,
                      member_steps: Optional[jax.Array] = None) -> jax.Array:
    """The S=1 window loop over a (K, B, P) block: pad once into the
    carry layout, the t=0 body-only launch, then per step the halo
    refresh and one megakernel launch, and slice once at the end.
    ``member_steps`` (K,) freezes member k once t reaches its own T.

    The scan runs at least two steps an iteration: a launch writes a new
    buffer, and with two launches XLA alternates the carry between two
    buffers where with one it copies the whole state back into the loop's
    buffer every step."""
    K, B, payload = local.shape

    def launch(c, wt):
        return _kops.taskbench_carry(c, wt, offset=lay.offset,
                                     payload=payload, **kw)

    pad_rows = ((0, 0), (0, lay.rows_p - B), (0, 0))
    with jax.named_scope("lane_pad"):
        carry = jnp.pad(local, ((0, 0),
                                (lay.offset, lay.rows_p - B + lay.offset),
                                (0, lay.lanes - payload)))
        wp, w0p = jnp.pad(w, pad_rows), jnp.pad(w0, pad_rows)
    carry = launch(carry, w0p)  # t=0: body only
    if steps > 1:
        def body(c, t):
            nxt = launch(_carry_halos(c, lay, num_devices), wp)
            if member_steps is not None:
                nxt = jnp.where((t < member_steps)[:, None, None], nxt, c)
            return nxt, None

        ts = None if member_steps is None else jnp.arange(1, steps)
        carry, _ = jax.lax.scan(body, carry, ts, length=steps - 1,
                                unroll=max(2, unroll))
    with jax.named_scope("lane_slice"):
        return carry[:, lay.offset:lay.offset + B, :payload]


def _rebase_rows(rel: jax.Array, *, row_axis: int = 0) -> jax.Array:
    """Signed window offsets -> absolute rows of THIS working buffer
    (``+ arange(M)``, clipped; the clip only ever binds on edge-garbage
    rows, which are never consumed by valid rows)."""
    m = rel.shape[row_axis]
    shape = [1] * rel.ndim
    shape[row_axis] = m
    rows = jnp.arange(m, dtype=jnp.int32).reshape(shape)
    return jnp.clip(rel + rows, 0, m - 1)


def _extend_tables(idx: jax.Array, wgt: jax.Array, depth: int,
                   num_devices: int, mode: str, *, row_axis: int = 0):
    """Deep-exchange the per-row operand tables ONCE for a blocked run.

    Weights (per global row, depth-invariant) extend exactly like state.
    Gather/onehot offset tables additionally rebase from signed offsets to
    absolute working-buffer rows (``_rebase_rows``). Window mode returns
    idx untouched (it is a dummy the kernel replaces).
    """
    wext = _extend_state(wgt, depth, num_devices, row_axis=row_axis)
    if mode == "window":
        return idx, wext
    rel = _extend_state(idx, depth, num_devices, row_axis=row_axis)
    return _rebase_rows(rel, row_axis=row_axis), wext


class _PhaseTables(NamedTuple):
    """Per-phase operand tables for one pipelined member (leading K axis).

    ``i_int``/``w_int`` cover the interior working buffer (the owned B
    rows); ``i_bnd``/``w_bnd`` cover the fused (K, 6*depth) boundary
    working buffer — rows [left buffer..., right buffer...] — matching
    ``taskbench_step_boundary``'s layout.
    """

    i_int: jax.Array
    w_int: jax.Array
    i_bnd: jax.Array
    w_bnd: jax.Array


def _phase_tables(idx: jax.Array, wgt: jax.Array, depth: int,
                  num_devices: int, mode: str) -> _PhaseTables:
    """Deep-exchange the tables once and slice them per pipeline phase.

    All arrays carry a leading K axis; rows live on axis 1. The extended
    table wext has B + 2*depth rows covering global rows [p0 - depth,
    p0 + B + depth): the interior buffer (owned rows [p0, p0 + B)) is
    wext[depth : depth + B], the left boundary buffer (rows [p0 - depth,
    p0 + 2*depth)) is wext[:3*depth], the right one wext[B - depth:].
    Gather/onehot offsets are rebased per buffer AFTER slicing — each
    phase's idx addresses its own working buffer.
    """
    K, B = wgt.shape[0], wgt.shape[1]

    def phases(ext):
        interior = jax.lax.slice_in_dim(ext, depth, depth + B, axis=1)
        boundary = jnp.concatenate([  # fused rows: [left 3d | right 3d]
            jax.lax.slice_in_dim(ext, 0, 3 * depth, axis=1),
            jax.lax.slice_in_dim(ext, B - depth, B + 2 * depth, axis=1),
        ], axis=1)
        return interior, boundary

    w_int, w_bnd = phases(_extend_state(wgt, depth, num_devices, row_axis=1))
    if mode == "window":  # idx is a dummy the kernel replaces
        i_int = jnp.zeros((K, 1, 1), jnp.int32)
        i_bnd = jnp.zeros((K, 1, 1), jnp.int32)
    else:
        rel_int, rel_bnd = phases(
            _extend_state(idx, depth, num_devices, row_axis=1))
        i_int = _rebase_rows(rel_int, row_axis=1)
        i_bnd = _rebase_rows(rel_bnd, row_axis=1)
    return _PhaseTables(i_int, w_int, i_bnd, w_bnd)


def _extend_launch(s: jax.Array, i: jax.Array, w: jax.Array, *, halo: int,
                   num_devices: int, kw: dict) -> jax.Array:
    """One S=1 step on (K, B, payload) state off the carry: ring-extend
    by ``halo`` rows each side, then one megakernel launch over the
    [halo | block | halo] rows."""
    return _kops.taskbench_step(
        _extend_state(s, halo, num_devices, row_axis=1), i, w, **kw)


def _serial_launch(s: jax.Array, iext: jax.Array, wext: jax.Array,
                   a: jax.Array, *, depth: int, num_devices: int,
                   kwb: dict) -> jax.Array:
    """One serial blocked launch on (K, B, payload) state: the deep
    exchange of ``depth`` rows each side, one S-step launch over the
    extended buffer (tables deep-exchanged once, `_extend_tables`), and
    the owned rows sliced back out."""
    B = s.shape[1]
    nf = _kops.taskbench_step(
        _extend_state(s, depth, num_devices, row_axis=1), iext, wext, a,
        **kwb)
    return jax.lax.slice_in_dim(nf, depth, depth + B, axis=1)


def _pipelined_launch(s, hl, hr, a, ph: _PhaseTables, depth: int,
                      num_devices: int, kwb: dict, impl: str = "xla"):
    """One software-pipelined blocked launch on stacked (K, B, payload)
    state. Steady-state schedule (DESIGN.md §6):

      1. boundary phase — consumes the halo received for THIS launch
         (``hl``/``hr``, issued at the end of the previous launch);
      2. the NEXT launch's deep exchange starts on the boundary outputs
         (they ARE the edge rows the neighbors need);
      3. the interior phase — no data dependence on the halo, the boundary
         launch, or the in-flight collective, so the scheduler may run the
         exchange under it.

    Returns (s_next, HaloHandle for the next launch).
    """
    B = s.shape[1]
    bl = jnp.concatenate(
        [hl, jax.lax.slice_in_dim(s, 0, 2 * depth, axis=1)], axis=1)
    br = jnp.concatenate(
        [jax.lax.slice_in_dim(s, B - 2 * depth, B, axis=1), hr], axis=1)
    bl_out, br_out = _kops.taskbench_boundary(
        bl, br, ph.i_bnd, ph.w_bnd, a, depth=depth, **kwb)
    handle = _halo.exchange_edges_start(
        bl_out, br_out, num_devices, AXIS, row_axis=1, impl=impl)
    mid = _kops.taskbench_interior(
        s, ph.i_int, ph.w_int, a, depth=depth, **kwb)
    return jnp.concatenate([bl_out, mid, br_out], axis=1), handle


def _prologue_exchange(state, depth, num_devices, impl: str = "xla"):
    """Start the FIRST blocked launch's exchange on the t=0 state's edges
    (the pipeline's fill step; the scan body then keeps one exchange in
    flight per launch)."""
    B = state.shape[1]
    return _halo.exchange_edges_start(
        jax.lax.slice_in_dim(state, 0, depth, axis=1),
        jax.lax.slice_in_dim(state, B - depth, B, axis=1),
        num_devices, AXIS, row_axis=1, impl=impl)


def _act_schedule(
    member_steps: Sequence[int], lockstep_steps: int, s: int
) -> np.ndarray:
    """(L, K, S) per-depth activity masks for the blocked launch loop.

    Launch l's inner step d executes lockstep timestep t = 1 + l*S + d;
    member k is active iff t < T_k (its own horizon) — the same predicate
    the per-step backends apply with `jnp.where`, here frozen INTO the
    launch schedule host-side. The final launch of any run carries the
    masked tail ((T-1) mod S trailing zeros for every member).
    """
    L = max(1, -(-(lockstep_steps - 1) // s)) if lockstep_steps > 1 else 0
    t = 1 + (np.arange(L)[:, None, None] * s + np.arange(s)[None, None, :])
    msteps = np.asarray(member_steps, np.int64)[None, :, None]
    return (t < msteps).astype(np.float32)


class _ResolvedPlan(NamedTuple):
    """What one graph will actually run: a plan kind + launch depth.

    ``reason`` names the verdict source when the resolution involved a
    cost-model judgment (plan re-routing, tuner declines) — empty for
    purely structural picks."""

    kind: str
    steps_per_launch: int
    reason: str = ""


@register
class PallasStepRuntime(_BspBase):
    name = "pallas_step"

    # ------------------------------------------------------ plan dispatch

    def _gather_width_cap(self) -> int:
        return int(self.options.get(
            "gather_width_cap", _schedule.DEFAULT_GATHER_WIDTH_CAP))

    def _cost_model(self, payload: Optional[int] = None):
        """The CostModel pricing this runtime's scheduling verdicts.

        The ``cost_model`` option (a CostModel, a to_dict()-shaped dict,
        or a cache-file path) is the EXPLICIT tier of the precedence;
        unset falls through to probes.default_cost_model (env > cached
        probes > analytic). Only ranks/sizes schedules — numerics are
        model-independent."""
        return _probes.coerce_cost_model(
            self.options.get("cost_model"),
            devices=len(self.devices), payload=payload)

    def plan_for(self, graph: TaskGraph) -> Tuple[Optional[str], str]:
        """pattern -> execution plan kind, or (None, reason).

        halo-expressible period-1 patterns take the halo plan (ring
        exchanges, every schedule above); butterfly patterns the stride
        plan (XOR block permutes); anything else — and butterfly when a
        blocked schedule is requested — the all-gather plan, capped at
        ``gather_width_cap`` rows.
        """
        D = len(self.devices)
        if graph.width % D != 0:
            return None, f"width {graph.width} not divisible by {D} devices"
        r = _patterns.halo_radius(graph)
        if r >= 0 and graph.period == 1:
            # no r <= block restriction: _halo.exchange_halos goes
            # multi-hop when a (deep) halo exceeds the local block
            return PLAN_HALO, ""
        if graph.pattern in _patterns.BUTTERFLY_PATTERNS and graph.width > 1:
            # W=1 degenerates to a pure self-dependency (partner = p XOR 1
            # falls outside the width), which breaks the stride plan's
            # exactly-two-deps tables; it falls through to the all-gather
            # plan (W=1 is always under the cap), whose tables come from
            # the graph's own dependency arrays and handle it exactly.
            return PLAN_STRIDE, ""
        cap = self._gather_width_cap()
        if graph.width <= cap:
            return PLAN_ALLGATHER, ""
        return None, (
            f"pattern {graph.pattern} at width {graph.width} fits no "
            f"pallas_step plan (halo: halo-expressible period-1 patterns "
            f"at any width; stride: butterfly fft/tree; allgather: any "
            f"pattern up to gather_width_cap={cap} rows) — fall back to "
            f"the `fused` backend, which runs every pattern at any width "
            f"[verdict source: "
            f"{self._cost_model(graph.payload).describe(graph.width)}]"
        )

    def supports(self, graph: TaskGraph):
        plan, why = self.plan_for(graph)
        return (True, "") if plan is not None else (False, why)

    def _schedule_for_graph(self, graph: TaskGraph) -> _ResolvedPlan:
        """The (plan, steps_per_launch) this runtime will execute.

        The stride plan is per-step by construction (see module
        docstring); an EXPLICIT blocked request on a butterfly graph
        re-routes to the all-gather plan when the width fits under the
        cap and the resolver actually grants a depth > 1 —
        `dispatches_per_run` reports whatever this returns, so launch
        accounting can never drift from the executed schedule."""
        plan, why = self.plan_for(graph)
        if plan is None:
            raise ValueError(
                f"runtime {self.name} cannot run {graph.describe()}: {why}")
        if plan == PLAN_HALO:
            return _ResolvedPlan(plan, self._graph_steps_per_launch(graph))
        opt = self.options.get("steps_per_launch")
        if plan == PLAN_STRIDE:
            # Two routes re-route a butterfly to the blocked all-gather
            # plan. An EXPLICIT depth (the user's ablation choice) always
            # did. "auto" newly can — but only under a MEASURED cost
            # model: the analytic rules cannot rank the plans
            # (gathered_pays_off compares blocked gathers against
            # per-step GATHERS, not against the stride plan it would
            # displace here, whose in-block slots need no collective and
            # whose pair combine is gather-free), while measured
            # launch/stride/gather/row-step walls can
            # (schedule.gathered_beats_strides). With the analytic
            # fallback "auto" keeps the stride plan — bit-identical to
            # the pre-measurement behavior.
            if opt in (None, 1):
                return _ResolvedPlan(plan, 1)
            if _schedule.is_auto(opt):
                if graph.width > self._gather_width_cap():
                    return _ResolvedPlan(plan, 1)
                model = self._cost_model(graph.payload)
                s = self._gathered_steps_per_launch(graph)
                if s <= 1:
                    return _ResolvedPlan(plan, 1)
                strides = _patterns.butterfly_slot_strides(graph)
                B = self._block(graph)
                beats, why = _schedule.gathered_beats_strides(
                    width=graph.width, block=B, steps_per_launch=s,
                    off_block_strides=sum(1 for st in strides if st >= B),
                    period=len(strides), model=model,
                    impl=self._halo_impl())
                if beats:
                    return _ResolvedPlan(PLAN_ALLGATHER, s, why)
                return _ResolvedPlan(plan, 1, why)
            if graph.width <= self._gather_width_cap():
                s = self._gathered_steps_per_launch(graph)
                if s > 1:
                    return _ResolvedPlan(PLAN_ALLGATHER, s,
                                         "explicit blocked request")
            return _ResolvedPlan(plan, 1)
        return _ResolvedPlan(plan, self._gathered_steps_per_launch(graph))

    def _gathered_steps_per_launch(self, graph: TaskGraph) -> int:
        return _schedule.resolve_steps_per_launch_gathered(
            self.options.get("steps_per_launch"),
            width=graph.width, block=self._block(graph),
            max_deps=graph.max_deps, payload=graph.payload,
            total_steps=graph.steps,
            combine=self._plan_combine(PLAN_ALLGATHER),
            # mirror what the launch actually holds: period-1 patterns
            # keep one static table pair, not S per-depth tables
            time_varying=graph.pattern == "spread" or graph.period > 1,
            model=self._cost_model(graph.payload),
        )

    # ------------------------------------------------------------ operands

    def _combine_mode(self) -> str:
        mode = str(self.options.get("combine", "window"))
        if mode not in ("window", "gather", "onehot"):
            # "pair" is in the kernel's COMBINE_MODES but is an INTERNAL
            # lowering the stride plan selects itself — as a runtime
            # option it would crash the halo plan's operand layout, so
            # every unknown/internal mode is rejected up front
            raise ValueError(
                f"unknown combine option {mode!r}: choose window, gather, "
                f"or onehot ('pair' is the stride plan's internal "
                f"lowering, selected automatically)")
        if mode == "gather" and jax.default_backend() == "tpu":
            # refused here, not deep in Mosaic, and never rewritten: an
            # explicit ablation must run what it names or fail
            raise ValueError(
                "combine='gather' cannot run on the TPU: Mosaic has no row "
                "gather. Use combine='onehot' (the same combine as an MXU "
                "matmul) or the default window combine")
        return mode

    def _plan_combine(self, plan: str) -> str:
        """Combine mode under a plan. halo honors the option as-is; the
        stride/allgather working buffers are gathered-row addressed, so
        the window (shifted-slice) combine cannot express them and the
        default ("window"/unset) resolves per plan:

          stride     "pair" — the partner row is materialized by an XOR
                     layout shuffle (in-block) or a block permute
                     (off-block), so the kernel's combine is an
                     elementwise (a + b) * 0.5: gather-free, exact, and
                     Mosaic-friendly (slices and adds only). This is the
                     butterfly analogue of the halo plan's window mode.
          allgather  "onehot" on TPU — the MXU lowering, since a Mosaic
                     row gather does not lower (DESIGN.md §4) —
                     and "gather" elsewhere, where fancy indexing lowers
                     fine and the onehot's (W, W) matrix build per step
                     is pure overhead.

        An explicit "gather"/"onehot" option is honored on both plans
        (the ablations); all selections are bit-identical per plan (same
        tables, same weights, exact 0.5 halving)."""
        mode = self._combine_mode()
        if plan == PLAN_HALO or mode in ("gather", "onehot"):
            return mode
        if plan == PLAN_STRIDE:
            return "pair"
        return "onehot" if jax.default_backend() == "tpu" else "gather"

    def _operands(self, graph: TaskGraph, halo: int,
                  block: Optional[int] = None):
        """Host-built (idx, wgt, idx0, wgt0) for one member graph (S=1).

        The t>=1 operands follow the selected combine mode; the t=0 (body
        only) call is always a 1-column self window, which is identical
        across modes (window offset 0 == gather of own row).
        ``block`` overrides the per-device row count (the K-sharded 2D
        mesh shards rows over Dr < D devices, so its blocks are larger
        than ``_block``'s 1D default).
        """
        B = self._block(graph) if block is None else block
        if self._combine_mode() == "window":
            idx, wgt = _window_operands(graph, halo)
        else:
            idx, wgt = _ext_dep_operands(graph, B, halo)
        idx0, wgt0 = _self_operands(graph.width, B)
        return idx, wgt, idx0, wgt0

    def _blocked_operands(self, graph: TaskGraph, halo: int,
                          block: Optional[int] = None):
        """Host-built (idx, wgt, idx0, wgt0) for the blocked path.

        Window mode reuses the per-global-row weight table; gather/onehot
        switch to SIGNED offsets (_rel_dep_operands) so the tables can be
        deep-halo-exchanged and rebased onto the working buffer in-scan.
        """
        B = self._block(graph) if block is None else block
        if self._combine_mode() == "window":
            idx, wgt = _window_operands(graph, halo)
        else:
            idx, wgt = _rel_dep_operands(graph)
        idx0, wgt0 = _self_operands(graph.width, B)
        return idx, wgt, idx0, wgt0

    def _kernel_kw(self, spec: KernelSpec, combine: Optional[str] = None) -> dict:
        kw = dict(
            kind=spec.kind, iterations=spec.iterations, scratch=spec.scratch,
            combine=combine or self._combine_mode(),
        )
        if self.options.get("block_rows"):
            kw["block_rows"] = int(self.options["block_rows"])
        return kw

    # ---------------------------------------------------------- pipelining

    def _pipeline_requested(self) -> bool:
        """``pipeline=False`` is the serial-exchange ablation (mirrors the
        overlap runtime's ``overlap=False``); default on."""
        return bool(self.options.get("pipeline", True))

    def _halo_impl(self) -> str:
        """Transport for the pipelined edge exchange: "xla" (fused
        single-collective default) or "ppermute" (per-direction; isolates
        the pure scheduling effect in ablations)."""
        return str(self.options.get("halo_impl", "xla"))

    def _gather_impl(self, width: int) -> str:
        """Transport for the all-gather plan's ``gather_global``.

        ``gather_impl`` option: an explicit registry name wins; "auto"
        (default) follows a non-default ``halo_impl`` (so ppermute/chaos
        ablations keep injecting into the gather, the pre-2D behavior)
        and otherwise asks the schedule layer to rank chunked vs
        monolithic at this (devices, width) — measured walls when the
        cost model has the devices-dimension probes, the ~sqrt(D)
        rendezvous heuristic past D >= 16 otherwise. Every choice is
        bit-identical; only the wall changes.
        """
        opt = str(self.options.get("gather_impl", "auto"))
        if opt != "auto":
            if opt not in _halo.GATHER_IMPLS:
                raise ValueError(
                    f"unknown gather impl {opt!r}; known "
                    f"{sorted(_halo.GATHER_IMPLS)}")
            return opt
        halo = self._halo_impl()
        if halo != "xla" and halo in _halo.GATHER_IMPLS:
            return halo
        impl, _reason = _schedule.choose_gather_impl(
            width=width, devices=len(self.devices),
            model=self._cost_model())
        return impl

    def _member_shards(self, ensemble: GraphEnsemble) -> int:
        """Resolved Dk for the stacked ensemble paths (``member_shards``
        option; default 1 = the replicated 1D row mesh). "auto" asks the
        schedule layer to price the (Dr, Dk) split. An explicit Dk that
        cannot shard this ensemble's K is rejected loudly here (the mesh
        builder rejects Dk not dividing the device count the same way)."""
        raw = self.options.get("member_shards", 1)
        K = len(ensemble.members)
        D = len(self.devices)
        if _schedule.is_auto(raw):
            g = ensemble.members[0]
            dk, _reason = _schedule.choose_member_shards(
                devices=D, num_members=K, width=g.width,
                steps_per_launch=self._ensemble_steps_per_launch(ensemble),
                radius=max(_patterns.halo_radius(m)
                           for m in ensemble.members),
                model=self._cost_model(g.payload))
            return dk
        dk = int(raw)
        if dk < 1:
            raise ValueError(f"member_shards must be >= 1, got {dk}")
        if dk == 1:
            return 1
        if K % dk:
            raise ValueError(
                f"member_shards={dk} does not divide this ensemble's "
                f"K={K} members — each member-axis shard needs an equal "
                f"K/Dk slice of the stacked (K, B, payload) state. Pass "
                f"member_shards=1 (or a divisor of {K}) to fall back to "
                f"the replicated 1D row mesh.")
        if D % dk:
            # same loud contract as make_row_member_mesh, raised before
            # any shard_map can fail with an opaque XLA error
            make_row_member_mesh(self.devices, dk, row_axis=AXIS,
                                 member_axis=MEMBER_AXIS)
        return dk

    def _stacked_mesh(self, ensemble: GraphEnsemble):
        """(mesh, dk, Dr) for the stacked paths: the 2D (row, member)
        mesh when member_shards > 1, else the 1D row mesh. Row-axis
        collectives span Dr = D / Dk devices either way (AXIS is the
        leading mesh axis in both)."""
        dk = self._member_shards(ensemble)
        D = len(self.devices)
        if dk == 1:
            return self._mesh(), 1, D
        mesh = make_row_member_mesh(self.devices, dk, row_axis=AXIS,
                                    member_axis=MEMBER_AXIS)
        return mesh, dk, D // dk

    def _pipeline_active(self, block: int, s: int, halo: int,
                         payload: Optional[int] = None) -> bool:
        """The pipelined schedule applies when blocking is on AND the owned
        block keeps a nonempty interior once 2*S*r edge rows belong to the
        boundary phase. Tiny blocks (block <= 2*S*r) have nothing to hide
        the exchange under — the regime where pipeline=False wins anyway by
        not paying the second launch — so they fall back to the serial
        schedule. Note S*r < block here, so the pipelined exchange is
        always single-hop. Under ``steps_per_launch="auto"`` the tuner's
        profitability verdict also binds (a fallback depth chosen with no
        covering candidate runs serial), priced by this runtime's cost
        model; an EXPLICIT S is the user's ablation choice and pipelines
        whenever structurally possible."""
        if not (s > 1 and halo > 0 and self._pipeline_requested()
                and block > 2 * s * halo):
            return False
        if _schedule.is_auto(self.options.get("steps_per_launch")):
            return _schedule.pipeline_interior_covers_exchange(
                block, halo, s, self._cost_model(payload))
        return True

    # ------------------------------------------------------- launch depth

    def _steps_per_launch(self, block: int, radius: int, payload: int,
                          total_steps: int) -> int:
        return _schedule.resolve_steps_per_launch(
            self.options.get("steps_per_launch"),
            block=block, radius=radius, payload=payload,
            total_steps=total_steps, combine=self._combine_mode(),
            pipeline=self._pipeline_requested(),
            model=self._cost_model(payload),
        )

    def _graph_steps_per_launch(self, graph: TaskGraph) -> int:
        return self._steps_per_launch(
            self._block(graph), _patterns.halo_radius(graph), graph.payload,
            graph.steps,
        )

    def _ensemble_steps_per_launch(self, ensemble: GraphEnsemble) -> int:
        """Common launch depth for an ensemble: one cadence for all members
        (launch boundaries are shared), so take the most conservative
        member's resolved depth. A member on a stride or all-gather plan
        pins the shared cadence to per-step (its exchanges are per-step /
        per-gather, and the deep-halo machinery does not apply to it)."""
        members = ensemble.members
        if any(self.plan_for(g)[0] != PLAN_HALO for g in members):
            return 1
        if self._is_stacked(ensemble):
            H = max(_patterns.halo_radius(g) for g in members)
            return self._steps_per_launch(
                self._block(members[0]), H, members[0].payload, ensemble.steps
            )
        return min(
            self._steps_per_launch(
                self._block(g), _patterns.halo_radius(g), g.payload,
                ensemble.steps,
            )
            for g in members
        )

    def stacking_verdict(self, ensemble: GraphEnsemble) -> Tuple[bool, str]:
        """``supports()``-style verdict for the stacked fast path: (ok,
        reason). Stacked launches share one (K, B, ...) operand set built
        by the halo-plan machinery, so they require uniform (width,
        payload), one kernel, and every member on the halo plan;
        everything else takes the slow per-step tuple fallback. The reason
        string names exactly which requirement failed so a packer (or a
        trace reader) can see WHY a cohort degraded instead of silently
        paying per-step dispatch."""
        members = ensemble.members
        reasons = []
        if not ensemble.stackable:
            widths = sorted({g.width for g in members})
            payloads = sorted({g.payload for g in members})
            reasons.append(
                f"members do not stack into one (K, W, payload) state: "
                f"widths {widths}, payloads {payloads}")
        kernels = {g.kernel for g in members}
        if len(kernels) != 1:
            reasons.append("mixed kernels: " + ", ".join(sorted(
                f"{k.kind}@it{k.iterations}" for k in kernels)))
        off_plan = []
        for i, g in enumerate(members):
            plan, why = self.plan_for(g)
            if plan != PLAN_HALO:
                off_plan.append(
                    f"member {i} ({g.pattern}) resolves the "
                    f"{plan or 'un-supported'} plan")
        if off_plan:
            reasons.append(
                "stacked operands are built by the halo-plan machinery: "
                + "; ".join(off_plan))
        if reasons:
            return False, "; ".join(reasons)
        return True, ("stacked: uniform (width, payload, kernel) and "
                      "every member on the halo plan")

    def _is_stacked(self, ensemble: GraphEnsemble) -> bool:
        return self.stacking_verdict(ensemble)[0]

    @staticmethod
    def _launches(total_steps: int, s: int) -> int:
        """Kernel launches for one member's run: the t=0 body-only launch
        plus ceil((T-1)/S) blocked combine launches."""
        if total_steps <= 1:
            return 1
        return 1 + -(-(total_steps - 1) // s)

    # ------------------------------------------------------- single graph

    def build(self, graph: TaskGraph) -> Callable[[jax.Array], jax.Array]:
        """Plan, build and place one graph's program — every plan goes
        through here. The layer spans (repro.obs.layer_span):
        ``pallas_step.build`` (attrs plan, S, width, grain) holds
        ``pallas_step.plan`` and the builder's ``pallas_step.operands``
        (host tables) and ``pallas_step.program`` (jit + shard_map); the
        returned callable's first call is ``pallas_step.first_call``
        (trace, lower, compile or cache read, enqueue), every later one
        ``pallas_step.call``."""
        self._require_support(graph)
        tr = self.tracer
        with layer_span(tr, "pallas_step.build", width=graph.width,
                        grain=graph.kernel.iterations) as span:
            with layer_span(tr, "pallas_step.plan"):
                plan = self._schedule_for_graph(graph)
            S = plan.steps_per_launch
            span.set(plan=plan.kind, S=S)
            if tr.enabled:
                _schedule.record_resolution(
                    tr, plan=plan.kind, steps_per_launch=S,
                    pipeline=plan.kind == PLAN_HALO and self._pipeline_active(
                        self._block(graph), S, _patterns.halo_radius(graph),
                        graph.payload),
                    model=self._cost_model(graph.payload),
                    reason=plan.reason, runtime=self.name,
                    pattern=graph.pattern, width=graph.width,
                    launches=self._launches(graph.steps, S))
            if plan.kind == PLAN_STRIDE or (
                    plan.kind == PLAN_ALLGATHER and S == 1):
                fn = self._build_plan_stepper(graph, plan.kind)
            elif plan.kind == PLAN_ALLGATHER:
                fn = self._build_allgather_blocked(graph, S)
            else:
                fn = self._build_halo_stacked(graph, S)
        return _with_call_spans(fn, tr)

    # ------------------------------------------------------------ halo plan

    def _build_halo_stacked(
        self, unit: TaskGraph | GraphEnsemble, S: int, *,
        tile: Optional[Tuple[int, int]] = None,
        carry: Optional[bool] = None,
    ) -> Callable:
        """The halo plan's one builder, for a single graph and for a
        stacked ensemble alike: the loop over a (K, B, payload) local
        state in one scanned program, every launch running all K members'
        combines and bodies, one ring exchange moving every member's
        halos.

        ``unit`` is a TaskGraph or a GraphEnsemble. A single graph is the
        one-member case on the 1D row mesh: its program takes and returns
        the (W, payload) state sharded ``P(AXIS)``, adding and dropping
        the member axis inside the shard_map. An ensemble's program takes
        the stacked (K, W, payload) state; with ``member_shards`` Dk > 1
        it runs over the 2D (row, member) mesh, each device holding a
        (K/Dk, W/Dr, payload) slice, and every halo exchange still names
        AXIS, spanning only its Dr-device row subgroup. Outputs are
        bit-identical either way (same per-row arithmetic, only ownership
        moves).

        The loop's form is chosen here, once:

          S = 1, carry  window combine, no ``block_rows``: the state rides
                        the scan in the megakernel's own layout
                        (`_window_carry_run`);
          S = 1, extend gather/onehot or a row grid: per step a ring
                        extend and one launch (`_extend_launch`);
          S > 1         pipelined when `_pipeline_active` (boundary and
                        interior phases, the next exchange under the
                        interior: `_pipelined_launch`), else serial
                        (`_serial_launch`).

        The program reads only its branch's operands, and freezes member
        k once t reaches its own T (``member_steps``, or the act
        schedule at S > 1). ``tile`` overrides the carry's (sublanes,
        lanes) tile, so the chip's (8, 128) layout also runs in interpret
        mode; ``carry`` forces the S = 1 branch (tests)."""
        single = isinstance(unit, TaskGraph)
        ens = GraphEnsemble([unit]) if single else unit
        members = ens.members
        K = len(members)
        unroll = int(self.options.get("unroll", 1))
        D = len(self.devices)
        mesh, dk, Dr = ((self._mesh(), 1, D) if single
                        else self._stacked_mesh(ens))
        H = max(_patterns.halo_radius(g) for g in members)
        B = members[0].width // Dr
        payload = members[0].payload
        steps = ens.steps
        kw = self._kernel_kw(members[0].kernel)
        mode = kw["combine"]
        if carry is None:
            carry = mode == "window" and "block_rows" not in kw
        xspec = (P(AXIS) if single else
                 P(MEMBER_AXIS, AXIS) if dk > 1 else P(None, AXIS))
        with layer_span(self.tracer, "pallas_step.operands"):
            operands = self._blocked_operands if S > 1 else self._operands
            ops4 = [operands(g, H, block=B) for g in members]
            idx, wgt, idx0, wgt0 = ops4[0] if single else _stack_operands(ops4)
            extra, espec = (), ()
            if S > 1:
                # (L, K, S): the member axis shards its K slices alongside
                # the state, so each device masks only the members it owns
                extra = (_act_schedule(ens.member_steps, steps, S),)
                espec = (P(None, MEMBER_AXIS) if dk > 1 else P(),)
            elif ens.heterogeneous_steps:
                extra = (np.asarray(ens.member_steps, np.int32),)
                espec = (P(MEMBER_AXIS) if dk > 1 else P(),)

        if S == 1 and carry:
            tables = (wgt, wgt0)
            lay = _carry_layout(B, H, payload, tile)
            ckw = {k: kw[k] for k in ("kind", "iterations", "scratch")}

            def local_run(local, w, w0, msteps=None):
                return _window_carry_run(
                    local, w, w0, lay=lay, steps=steps, num_devices=Dr,
                    kw=ckw, unroll=unroll, member_steps=msteps)
        elif S == 1:
            tables = (idx, wgt, idx0, wgt0)

            def local_run(local, i, w, i0, w0, msteps=None):
                state = _kops.taskbench_step(local, i0, w0, **kw)  # t=0
                if steps == 1:
                    return state

                def body(s, t):
                    nxt = _extend_launch(s, i, w, halo=H, num_devices=Dr,
                                         kw=kw)
                    if msteps is not None:
                        nxt = jnp.where((t < msteps)[:, None, None], nxt, s)
                    return nxt, None

                ts = None if msteps is None else jnp.arange(1, steps)
                state, _ = jax.lax.scan(body, state, ts, length=steps - 1,
                                        unroll=unroll)
                return state
        else:
            tables = (idx, wgt, idx0, wgt0)
            depth = S * H
            kwb = dict(kw, steps_per_launch=S)
            kwb.pop("block_rows", None)  # blocked path: one program a member
            pipelined = self._pipeline_active(B, S, H, payload)
            impl = self._halo_impl()

            def local_run(local, i, w, i0, w0, act_seq):  # (L, K, S) acts
                state = _kops.taskbench_step(local, i0, w0, **kw)  # t=0
                if steps == 1:
                    return state
                if pipelined:
                    ph = _phase_tables(i, w, depth, Dr, mode)
                    h = _prologue_exchange(state, depth, Dr, impl)

                    def pbody(c, a):
                        s, hl, hr = c
                        s2, h2 = _pipelined_launch(
                            s, hl, hr, a, ph, depth, Dr, kwb, impl)
                        return (s2, h2.recv_left, h2.recv_right), None

                    (state, _, _), _ = jax.lax.scan(
                        pbody, (state, h.recv_left, h.recv_right), act_seq,
                        unroll=unroll)
                    return state
                # the per-row operand tables are deep-exchanged ONCE: every
                # working row then owns its exact (edge-clipped) weights
                iext, wext = _extend_tables(i, w, depth, Dr, mode, row_axis=1)

                def body(s, a):
                    return _serial_launch(s, iext, wext, a, depth=depth,
                                          num_devices=Dr, kwb=kwb), None

                state, _ = jax.lax.scan(body, state, act_seq, unroll=unroll)
                return state

        n = len(tables)
        if single:  # the member axis is added and dropped inside
            run_stacked = local_run

            # wraps keeps run_stacked's parameter names, which name the
            # compiled program's parameters
            @functools.wraps(run_stacked)
            def local_run(local, *ops):
                return run_stacked(local[None], *(o[None] for o in ops[:n]),
                                   *ops[n:])[0]

        with layer_span(self.tracer, "pallas_step.program"):
            fn = jax.jit(
                shard_map(
                    local_run, mesh=mesh, check_vma=False,
                    in_specs=(xspec,) * (1 + n) + espec, out_specs=xspec,
                )
            )
        sh = NamedSharding(mesh, xspec)
        consts = tuple(
            jax.device_put(jnp.asarray(a), sh) for a in tables
        ) + tuple(jax.device_put(jnp.asarray(a), NamedSharding(mesh, s))
                  for a, s in zip(extra, espec))
        if single:
            return lambda init: fn(jax.device_put(init, sh), *consts)

        def run(inits):
            out = fn(jax.device_put(jnp.stack(inits), sh), *consts)
            return tuple(out[k] for k in range(K))

        return run
    # ------------------------------------------- stride / all-gather plans

    def _stride_levels(
        self, graph: TaskGraph, *, tile: Optional[Tuple[int, int]] = None
    ) -> Tuple[Callable, Tuple[Callable, ...], Tuple[int, ...]]:
        """(t0, branches, slot_branch) for one stride-plan (butterfly)
        member: the single definition of a level, which both the
        ensemble step's switch and the single-graph straight-line loop
        call.

        ``branches[j](s)`` runs one level at the j-th distinct pairing
        distance — in-block strides gather locally, block strides first
        XOR-permute the partner block in (`_halo.exchange_stride`) — and
        one megakernel launch combines {p, partner} and runs the body;
        period slot i runs ``branches[slot_branch[i]]``. Tables are
        device-invariant (XOR structure is translation-invariant across
        blocks), so they ride as closures. Under the pair combine the
        in-block swap is the named scope ``xor_swap`` and the
        [x | partner] stack ``pair_src``. ``tile`` (sublanes, lanes)
        overrides the launch's padding tile, which is the chip's (8, 128)
        on the TPU and (1, 1) in interpret mode."""
        D = len(self.devices)
        B = self._block(graph)
        mode = self._plan_combine(PLAN_STRIDE)
        kw = self._kernel_kw(graph.kernel, combine=mode)
        if tile is not None:
            kw["_tile"] = tile
        impl = self._halo_impl()
        strides = _patterns.butterfly_slot_strides(graph)
        distinct = sorted(set(strides))
        # pair mode's idx/wgt are kernel-side dummies (wgt's row count
        # declares the output width); table modes carry real slot tables
        dummy_i = jnp.zeros((1, 1), jnp.int32)
        dummy_w = jnp.zeros((B, 1), WEIGHT_DTYPE)

        def make_branch(s: int) -> Callable:
            if mode == "pair":
                if s < B:
                    def partner_of(local):
                        with jax.named_scope("xor_swap"):
                            return _xor_swap(local, s)
                else:
                    bs = s // B

                    def partner_of(local):
                        p, = _halo.exchange_stride(
                            local, (bs,), D, AXIS, impl=impl)
                        return p

                def branch(local):
                    partner = partner_of(local)
                    with jax.named_scope("pair_src"):
                        src = jnp.concatenate([local, partner], axis=0)
                    return _kops.taskbench_step(
                        src[None], dummy_i[None], dummy_w[None], **kw)[0]

                return branch
            idx_np, wgt_np, off_block = _stride_slot_tables(B, s)
            idx, wgt = jnp.asarray(idx_np), jnp.asarray(wgt_np)
            if not off_block:
                def branch(local):
                    return _kops.taskbench_step(
                        local[None], idx[None], wgt[None], **kw)[0]
            else:
                bs = s // B

                def branch(local):
                    partner, = _halo.exchange_stride(
                        local, (bs,), D, AXIS, impl=impl)
                    src = jnp.concatenate([local, partner], axis=0)
                    return _kops.taskbench_step(
                        src[None], idx[None], wgt[None], **kw)[0]
            return branch

        branches = tuple(make_branch(s) for s in distinct)
        slot_branch = tuple(distinct.index(s) for s in strides)
        i0, w0 = _self_tables(B)

        if mode == "pair":
            # t=0 (body only) through pair itself: [x | x] halves give
            # (a + a) * 0.5 == a bit-exactly, so the stride plan never
            # leaves its gather-free lowering (a gather here would be the
            # one Mosaic-unfriendly op on an otherwise portable path)
            def t0(s, o):
                with jax.named_scope("pair_src"):
                    src = jnp.concatenate([s, s], axis=0)
                return _kops.taskbench_step(
                    src[None], dummy_i[None], dummy_w[None], **kw)[0]
        else:
            def t0(s, o):
                return _kops.taskbench_step(
                    s[None], i0[None], w0[None], **kw)[0]

        return t0, branches, slot_branch

    def _stride_step_fns(
        self, graph: TaskGraph, *, tile: Optional[Tuple[int, int]] = None
    ) -> Tuple[Callable, Callable]:
        """(t0, step) closures for one stride-plan member of a tuple
        ensemble, whose cadence is shared step by step with halo members.

        ``step(s, o, t)`` runs timestep t: a ``lax.switch`` on the period
        slot (t-1) mod period picks the level (`_stride_levels`); ``o``
        is an unused operand slot kept for signature parity with the halo
        members. A single graph runs its levels straight-line instead
        (`_build_plan_stepper`)."""
        t0, branches, slot_branch = self._stride_levels(graph, tile=tile)
        if len(branches) == 1:
            def step(s, o, t):
                return branches[0](s)
        else:
            period = graph.period
            bmap = jnp.asarray(slot_branch, jnp.int32)

            def step(s, o, t):
                slot = jax.lax.rem(t - 1, period)
                return jax.lax.switch(bmap[slot], branches, s)

        return t0, step

    def _global_table_fn(self, graph: TaskGraph) -> Tuple[Callable, bool]:
        """(tables_for, time_varying) — THE global-table policy, shared by
        the per-step and blocked all-gather builders so the two schedules
        cannot diverge.

        time_varying=True: ``tables_for(ts)`` maps a traced (n,) vector
        of timesteps to stacked (n, W, D) idx/wgt tables — spread rotates
        its base table by +(t-1) (the dependence set shifts rigidly;
        weights never rotate), other patterns gather their period stack
        at slots (ts-1) mod period. time_varying=False (period-1
        patterns, e.g. all_to_all): ``tables_for(None)`` returns the one
        static (W, D) pair."""
        W = graph.width
        if graph.pattern == "spread":
            bi, bw = _spread_base_operands(graph)
            base_i, base_w = jnp.asarray(bi), jnp.asarray(bw)

            def tables_for(ts):
                i_t = jnp.mod(base_i[None] + (ts - 1)[:, None, None], W)
                w_t = jnp.broadcast_to(
                    base_w[None], (ts.shape[0],) + base_w.shape)
                return i_t, w_t

            return tables_for, True
        gi, gw = _global_slot_operands(graph)
        tab_i, tab_w = jnp.asarray(gi), jnp.asarray(gw)
        period = gi.shape[0]
        if period == 1:
            def tables_for(ts):
                return tab_i[0], tab_w[0]

            return tables_for, False

        def tables_for(ts):
            slots = jnp.mod(ts - 1, period)
            return (jnp.take(tab_i, slots, axis=0),
                    jnp.take(tab_w, slots, axis=0))

        return tables_for, True

    def _allgather_step_fns(self, graph: TaskGraph) -> Tuple[Callable, Callable]:
        """(t0, step) closures for one all-gather-plan (global) member.

        ``step(s, o, t)``: gather the full global-order state, pick
        timestep t's (idx, wgt) tables (``_global_table_fn``), slice this
        device's output rows out of the global tables, one megakernel
        launch. Tables ride as closures (global tables are
        device-invariant; the per-device slice happens in-scan).

        Uniform all_to_all skips the gather entirely (``psum_mean``
        option, default on): every row's combine is the same global mean,
        so one psum of the local row-sums replaces the O(W) replication —
        within float32 reduction tolerance of the gathered combine, not
        bit-identical (summation order differs)."""
        D = len(self.devices)
        B = self._block(graph)
        W = graph.width
        kw = self._kernel_kw(graph.kernel,
                             combine=self._plan_combine(PLAN_ALLGATHER))
        impl = self._gather_impl(W)
        tables_for, time_varying = self._global_table_fn(graph)
        i0, w0 = _self_tables(B)

        def t0(s, o):
            return _kops.taskbench_step(s[None], i0[None], w0[None], **kw)[0]

        if (graph.pattern == "all_to_all"
                and bool(self.options.get("psum_mean", True))):

            def step(s, o, t):
                mean = _halo.global_mean(s, W, D, AXIS)
                src = jnp.broadcast_to(mean[None, :], (B, mean.shape[0]))
                # self tables on the combined rows: the same body-only
                # launch shape as t0 (combine of src[p] is src[p] itself)
                return _kops.taskbench_step(
                    src[None], i0[None], w0[None], **kw)[0]

            return t0, step

        def step(s, o, t):
            full = _halo.gather_global(s, D, AXIS, impl=impl)
            if time_varying:
                i_ts, w_ts = tables_for(jnp.reshape(t, (1,)))
                i_t, w_t = i_ts[0], w_ts[0]
            else:
                i_t, w_t = tables_for(None)
            r0 = jax.lax.axis_index(AXIS) * B
            i_loc = jax.lax.dynamic_slice_in_dim(i_t, r0, B, axis=0)
            w_loc = jax.lax.dynamic_slice_in_dim(w_t, r0, B, axis=0)
            return _kops.taskbench_step(
                full[None], i_loc[None], w_loc[None], **kw)[0]

        return t0, step

    def _member_step_fns(self, graph: TaskGraph,
                         plan: str) -> Tuple[tuple, Callable, Callable]:
        """(operands, t0, step) for one member of a tuple ensemble on its
        own plan, as `_build_ensemble_tuple` and `_launch_plan_stepwise`
        run it: ``t0(s, o)`` is the body-only launch and ``step(s, o,
        t)`` timestep t, on the member's (B, payload) block with ``o``
        its sharded operands. A halo member carries its host tables
        (`_operands`) and steps by `_extend_launch`; stride and
        all-gather members carry closure tables and an empty slot."""
        if plan == PLAN_STRIDE:
            return ((),) + self._stride_step_fns(graph)
        if plan == PLAN_ALLGATHER:
            return ((),) + self._allgather_step_fns(graph)
        H = _patterns.halo_radius(graph)
        kw = self._kernel_kw(graph.kernel)
        D = len(self.devices)

        def t0(s, o):
            return _kops.taskbench_step(
                s[None], o[2][None], o[3][None], **kw)[0]

        def step(s, o, t):
            return _extend_launch(s[None], o[0][None], o[1][None], halo=H,
                                  num_devices=D, kw=kw)[0]

        return self._operands(graph, H), t0, step

    def _build_plan_stepper(
        self, graph: TaskGraph, plan: str, *,
        tile: Optional[Tuple[int, int]] = None
    ) -> Callable:
        """Single-graph scan for the stride / all-gather plans: one
        megakernel launch (plus at most one collective) per timestep,
        whole loop in one jit — the same dispatch shape as the halo S=1
        path, with the plan's own exchange.

        The stride plan runs its period straight-line: with P the period
        and (q, r) = divmod(T-1, P), q scan trips each call levels
        0..P-1 in order, each a direct call of its level
        (`_stride_levels`) at a static stride, then levels 0..r-1 run
        after the scan. No switch and no traced slot index: compiled for
        the TPU, a switch returns each level's result through HBM and
        copies it back to VMEM every step, while straight-line levels
        keep the state in VMEM. ``unroll`` counts scan trips: periods for the
        stride plan, steps for the all-gather plan. The
        ``pallas_step.program`` span carries the stride plan's ``period``,
        ``period_trips`` (q) and ``remainder_levels`` (r). ``tile`` as in
        `_stride_levels`."""
        unroll = int(self.options.get("unroll", 1))
        mesh = self._mesh()
        T = graph.steps
        loop_attrs = {}
        with layer_span(self.tracer, "pallas_step.operands"):
            if plan == PLAN_STRIDE:
                t0, branches, slot_branch = self._stride_levels(
                    graph, tile=tile)
                levels = [branches[b] for b in slot_branch]
                trips, rem = divmod(T - 1, len(levels))
                loop_attrs = dict(period=len(levels), period_trips=trips,
                                  remainder_levels=rem)

                def run_steps(state):
                    def body(s, _):
                        for level in levels:
                            s = level(s)
                        return s, None

                    if trips:
                        state, _ = jax.lax.scan(body, state, None,
                                                length=trips, unroll=unroll)
                    for level in levels[:rem]:
                        state = level(state)
                    return state
            else:
                t0, step = self._allgather_step_fns(graph)

                def run_steps(state):
                    def body(s, t):
                        return step(s, (), t), None

                    state, _ = jax.lax.scan(
                        body, state, jnp.arange(1, T), unroll=unroll)
                    return state

        def local_run(local):
            state = t0(local, ())
            return state if T == 1 else run_steps(state)

        with layer_span(self.tracer, "pallas_step.program", **loop_attrs):
            fn = jax.jit(
                shard_map(local_run, mesh=mesh, check_vma=False,
                          in_specs=P(AXIS), out_specs=P(AXIS)))
        sh = NamedSharding(mesh, P(AXIS))
        return lambda init: fn(jax.device_put(init, sh))

    def _build_allgather_blocked(self, graph: TaskGraph, S: int) -> Callable:
        """Blocked all-gather plan: ONE full-state gather + one S-depth
        launch per ``ceil((T-1)/S)`` launches, with time-varying (S, W, D)
        idx/wgt tables driving the per-depth combine (butterfly slots /
        spread's rotation; period-1 patterns keep static tables). Every
        row of the gathered buffer advances exactly — the buffer is closed
        under any dependence set — so there is no valid-span shrink and
        the device slices its own rows from the final buffer. The act
        machinery (masked tail) is the halo path's, unchanged."""
        unroll = int(self.options.get("unroll", 1))
        mesh = self._mesh()
        D = len(self.devices)
        B = self._block(graph)
        T = graph.steps
        kw0 = self._kernel_kw(graph.kernel,
                              combine=self._plan_combine(PLAN_ALLGATHER))
        kwb = dict(kw0, steps_per_launch=S)
        kwb.pop("block_rows", None)
        impl = self._gather_impl(graph.width)
        with layer_span(self.tracer, "pallas_step.operands"):
            tables_for, time_varying = self._global_table_fn(graph)
            acts = _act_schedule((T,), T, S)[:, 0]  # (L, S)
            # first timestep of each launch (selects the depth tables in-scan)
            t0s = 1 + np.arange(acts.shape[0], dtype=np.int32) * S
        i0, w0 = _self_tables(B)

        def local_run(local, act_seq, t0_seq):
            state = _kops.taskbench_step(
                local[None], i0[None], w0[None], **kw0)[0]
            if T == 1:
                return state

            def body(s, inp):
                a, tt0 = inp
                full = _halo.gather_global(s, D, AXIS, impl=impl)
                if time_varying:
                    # this launch's S timesteps -> (S, W, D) depth tables
                    i_t, w_t = tables_for(tt0 + jnp.arange(S))
                else:
                    i_t, w_t = tables_for(None)
                nf = _kops.taskbench_step(
                    full[None], i_t[None], w_t[None], a[None], **kwb)[0]
                r0 = jax.lax.axis_index(AXIS) * B
                return jax.lax.dynamic_slice_in_dim(nf, r0, B, axis=0), None

            state, _ = jax.lax.scan(
                body, state, (act_seq, t0_seq), unroll=unroll)
            return state

        with layer_span(self.tracer, "pallas_step.program"):
            fn = jax.jit(
                shard_map(local_run, mesh=mesh, check_vma=False,
                          in_specs=(P(AXIS), P(), P()), out_specs=P(AXIS)))
        sh = NamedSharding(mesh, P(AXIS))
        rep = NamedSharding(mesh, P())
        acts_dev = jax.device_put(jnp.asarray(acts), rep)
        t0_dev = jax.device_put(jnp.asarray(t0s), rep)
        return lambda init: fn(jax.device_put(init, sh), acts_dev, t0_dev)

    # ---------------------------------------------------------- ensembles

    def build_ensemble(self, ensemble: GraphEnsemble) -> Callable:
        self._require_ensemble_support(ensemble)
        S = self._ensemble_steps_per_launch(ensemble)
        if self._is_stacked(ensemble):
            return self._build_halo_stacked(ensemble, S)
        self._record_stacking_degradation(ensemble, S, "tuple")
        if S > 1:
            return self._build_ensemble_tuple_blocked(ensemble, S)
        return self._build_ensemble_tuple(ensemble)

    def _record_stacking_degradation(self, ensemble: GraphEnsemble,
                                     S: int, plan_kind: str) -> None:
        """Decision record for a multi-member ensemble that fell off the
        stacked fast path. The fall used to be silent — cadence quietly
        pinned to per-step tuple dispatch — so every builder that takes
        the fallback emits one ``schedule.resolve`` instant naming the
        failed requirement (stacking_verdict's reason)."""
        if len(ensemble.members) <= 1:
            return
        if not getattr(self.tracer, "enabled", False):
            return
        ok, why = self.stacking_verdict(ensemble)
        if ok:
            return
        _schedule.record_resolution(
            self.tracer,
            plan=plan_kind,
            steps_per_launch=S,
            pipeline=False,
            model=self._cost_model(ensemble.members[0].payload),
            reason=f"ensemble off the stacked fast path: {why}",
            runtime=self.name,
            members=len(ensemble.members),
            stacked=False,
        )

    def _build_ensemble_tuple(self, ensemble: GraphEnsemble) -> Callable:
        """Mixed specs/shapes/plans: one launch per member, one jitted scan.

        Every member contributes a ``(t0, step)`` pair for its own plan:
        halo members keep the sharded-operand tables flowing through
        in_specs; stride and all-gather members carry device-invariant
        closure tables and an empty operand slot, and their step fns take
        the traced timestep (slot selection / rotation)."""
        members = ensemble.members
        unroll = int(self.options.get("unroll", 1))
        mesh = self._mesh()
        steps = ensemble.steps
        ops4, t0_fns, step_fns = zip(*(
            self._member_step_fns(g, self.plan_for(g)[0]) for g in members))

        def local_run(states, operands):
            states = tuple(
                f(s, o) for f, s, o in zip(t0_fns, states, operands)
            )
            if steps == 1:
                return states

            def body(ss, t):
                nxt = []
                for k, (s, o) in enumerate(zip(ss, operands)):
                    n = step_fns[k](s, o, t)
                    if members[k].steps < steps:
                        n = jnp.where(t < members[k].steps, n, s)
                    nxt.append(n)
                return tuple(nxt), None

            states, _ = jax.lax.scan(
                body, states, jnp.arange(1, steps), unroll=unroll
            )
            return states

        fn = jax.jit(
            shard_map(
                local_run, mesh=mesh, check_vma=False,
                in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS),
            )
        )
        sh = NamedSharding(mesh, P(AXIS))
        consts = tuple(
            tuple(jax.device_put(jnp.asarray(a), sh) for a in o) for o in ops4
        )
        return lambda inits: fn(
            tuple(jax.device_put(x, sh) for x in inits), consts
        )

    def _build_ensemble_tuple_blocked(
        self, ensemble: GraphEnsemble, S: int
    ) -> Callable:
        """Mixed specs/shapes, blocked: one S-step launch per member per
        scan iteration, launch cadence (and act schedule) shared."""
        members = ensemble.members
        K = len(members)
        unroll = int(self.options.get("unroll", 1))
        mesh = self._mesh()
        D = len(self.devices)
        steps = ensemble.steps
        mode = self._combine_mode()
        halos = [_patterns.halo_radius(g) for g in members]
        depths = [S * h for h in halos]
        kws = [self._kernel_kw(g.kernel) for g in members]
        kwbs = [dict(kw, steps_per_launch=S) for kw in kws]
        for kwb in kwbs:
            kwb.pop("block_rows", None)
        ops4 = [self._blocked_operands(g, h) for g, h in zip(members, halos)]
        acts = _act_schedule(ensemble.member_steps, steps, S)  # (L, K, S)
        # per-member pipeline gate: the cadence is shared, but a member with
        # no interior at depth S*h_k keeps the serial exchange inside the
        # same scan body
        piped = [
            self._pipeline_active(self._block(g), S, h, g.payload)
            for g, h in zip(members, halos)
        ]
        impl = self._halo_impl()

        def local_run(states, operands, act_seq):
            states = tuple(
                _kops.taskbench_step(s[None], o[2][None], o[3][None], **kw)[0]
                for s, o, kw in zip(states, operands, kws)
            )
            if steps == 1:
                return states

            exts = []   # serial members: deep-exchanged (iext, wext) tables
            phs = []    # pipelined members: per-phase tables
            halos0 = []  # pipelined members: the fill-step exchange
            for k, (s, o) in enumerate(zip(states, operands)):
                if piped[k]:
                    exts.append(None)
                    phs.append(_phase_tables(
                        o[0][None], o[1][None], depths[k], D, mode))
                    h = _prologue_exchange(s[None], depths[k], D, impl)
                    halos0.append((h.recv_left, h.recv_right))
                else:
                    exts.append(_extend_tables(o[0], o[1], depths[k], D, mode))
                    phs.append(None)
                    halos0.append(())

            def body(carry, a):  # a: (K, S)
                ss, hh = carry
                nxt, nh = [], []
                for k, s in enumerate(ss):
                    dep = depths[k]
                    if piped[k]:
                        hl, hr = hh[k]
                        s2, h2 = _pipelined_launch(
                            s[None], hl, hr, a[k][None], phs[k], dep, D,
                            kwbs[k], impl)
                        nxt.append(s2[0])
                        nh.append((h2.recv_left, h2.recv_right))
                        continue
                    iext, wext = exts[k]
                    nxt.append(_serial_launch(
                        s[None], iext[None], wext[None], a[k][None],
                        depth=dep, num_devices=D, kwb=kwbs[k])[0])
                    nh.append(())
                return (tuple(nxt), tuple(nh)), None

            (states, _), _ = jax.lax.scan(
                body, (states, tuple(halos0)), act_seq, unroll=unroll)
            return states

        fn = jax.jit(
            shard_map(
                local_run, mesh=mesh, check_vma=False,
                in_specs=(P(AXIS), P(AXIS), P()), out_specs=P(AXIS),
            )
        )
        sh = NamedSharding(mesh, P(AXIS))
        rep = NamedSharding(mesh, P())
        consts = tuple(
            tuple(jax.device_put(jnp.asarray(a), sh) for a in o) for o in ops4
        )
        acts_dev = jax.device_put(jnp.asarray(acts), rep)
        return lambda inits: fn(
            tuple(jax.device_put(x, sh) for x in inits), consts, acts_dev
        )

    # ----------------------------------------------------------- resilience

    def build_ensemble_launches(
        self, ensemble: GraphEnsemble
    ) -> EnsembleLaunchPlan:
        """Expose the ensemble's real launch structure for the resilience
        engine (base.EnsembleLaunchPlan): stacked halo ensembles keep
        their blocked cadence with the SERIAL exchange schedule (launch
        boundaries must be host-visible, and the serial schedule is
        bit-identical to the pipelined one — tests lock that in), mixed
        ensembles run the tuple step fns at per-step cadence. Either way
        each launch is one pure jitted function of (carry, act row), so
        replay-from-snapshot is bit-identical by construction."""
        self._require_ensemble_support(ensemble)
        if self._is_stacked(ensemble):
            return self._launch_plan_stacked(
                ensemble, self._ensemble_steps_per_launch(ensemble))
        self._record_stacking_degradation(ensemble, 1, "stepwise")
        return self._launch_plan_stepwise(ensemble)

    def _launch_plan_stacked(
        self, ensemble: GraphEnsemble, S: int
    ) -> EnsembleLaunchPlan:
        """Host-stepped twin of `_build_halo_stacked` on an ensemble: the
        same launches (`_extend_launch` at S=1, `_serial_launch` past
        it), operands and act predicate, with the scan unrolled to the
        host so the engine owns the launch loop; past S=1 the schedule
        is always the serial one."""
        members = ensemble.members
        K = len(members)
        mesh, dk, Dr = self._stacked_mesh(ensemble)
        B = members[0].width // Dr
        H = max(_patterns.halo_radius(g) for g in members)
        depth = S * H
        mode = self._combine_mode()
        kw0 = self._kernel_kw(members[0].kernel)
        steps = ensemble.steps
        acts = _act_schedule(ensemble.member_steps, steps, S)  # (L, K, S)
        kspec = P(MEMBER_AXIS, AXIS) if dk > 1 else P(None, AXIS)
        # the act row (K, S) shards its K slices with the state, so the
        # engine's host-side eviction edits (acts[l:, k, :] = 0) land on
        # exactly the member-shard that owns slot k
        aspec = P(MEMBER_AXIS) if dk > 1 else P()
        # admitted init rows replicate over the member axis (only the
        # owning shard writes them) and row-shard over AXIS
        ispec = P(None, AXIS)

        kwb = dict(kw0, steps_per_launch=S)
        kwb.pop("block_rows", None)
        operands = self._blocked_operands if S > 1 else self._operands
        idx, wgt, idx0, wgt0 = _stack_operands(
            [operands(g, H, block=B) for g in members])

        def t0_local(local, i0, w0):  # (K, B, P)
            return _kops.taskbench_step(local, i0, w0, **kw0)

        def launch_local(s, i, w, a):  # a: (K, S), K-sharded with state
            if S > 1:
                iext, wext = _extend_tables(i, w, depth, Dr, mode, row_axis=1)
                return _serial_launch(s, iext, wext, a, depth=depth,
                                      num_devices=Dr, kwb=kwb)
            nxt = _extend_launch(s, i, w, halo=H, num_devices=Dr, kw=kw0)
            # per-member freeze: same predicate the stacked scan applies
            # (act row at S=1 is exactly t < T_k)
            return jnp.where(a[:, 0][:, None, None] > 0, nxt, s)

        def admit_local(s, init, i0, w0, slot):  # init: (1, B, P)
            t0 = _kops.taskbench_step(init, i0[:1], w0[:1], **kw0)
            if dk > 1:
                # global slot -> this member-shard's local K range; only
                # the owning shard commits the update (clamped slice +
                # where keeps everything shape-static under shard_map)
                kl = s.shape[0]
                loc = slot - jax.lax.axis_index(MEMBER_AXIS) * kl
                owned = jnp.logical_and(loc >= 0, loc < kl)
                upd = jax.lax.dynamic_update_slice_in_dim(
                    s, t0, jnp.clip(loc, 0, kl - 1), axis=0)
                return jnp.where(owned, upd, s)
            return jax.lax.dynamic_update_slice_in_dim(s, t0, slot, axis=0)

        sh = NamedSharding(mesh, kspec)
        rep = NamedSharding(mesh, aspec)
        t0_fn = jax.jit(shard_map(
            t0_local, mesh=mesh, check_vma=False,
            in_specs=(kspec,) * 3, out_specs=kspec))
        launch = jax.jit(shard_map(
            launch_local, mesh=mesh, check_vma=False,
            in_specs=(kspec,) * 3 + (aspec,), out_specs=kspec))
        admit = jax.jit(shard_map(
            admit_local, mesh=mesh, check_vma=False,
            in_specs=(kspec, ispec) + (kspec,) * 2 + (P(),),
            out_specs=kspec))
        consts = tuple(
            jax.device_put(jnp.asarray(a), sh) for a in (idx, wgt, idx0, wgt0))

        def init_fn(inits):
            return t0_fn(jax.device_put(jnp.stack(inits), sh),
                         consts[2], consts[3])

        def launch_fn(carry, act_row, t0):
            del t0  # stacked halo tables are time-invariant
            return launch(carry, consts[0], consts[1],
                          jax.device_put(act_row, rep))

        def admit_fn(carry, slot, init):
            return admit(carry, jax.device_put(init[None], sh),
                         consts[2], consts[3],
                         jnp.asarray(slot, jnp.int32))

        model = self._cost_model(members[0].payload)
        return EnsembleLaunchPlan(
            steps_per_launch=S,
            member_steps=tuple(ensemble.member_steps),
            acts=acts,
            init_fn=init_fn,
            launch_fn=launch_fn,
            finalize=lambda carry: tuple(carry[k] for k in range(K)),
            admit_fn=admit_fn,
            expected_launch_us=_schedule.expected_launch_wall_us(
                rows=(K // dk) * B, steps_per_launch=S, model=model,
                impl=self._halo_impl()),
            kind="stacked",
            # launch shapes are membership-invariant (evict/admit only
            # edit mask/state VALUES) so this cache must never grow past
            # its first entry — the serving fabric asserts exactly that
            compile_counter=launch._cache_size,
        )

    def _launch_plan_stepwise(
        self, ensemble: GraphEnsemble
    ) -> EnsembleLaunchPlan:
        """Per-step cadence for mixed-plan/heterogeneous ensembles: the
        tuple path's (t0, step) fns with the launch loop on the host and
        the freeze predicate driven by the act schedule (so eviction is
        the same mask edit as the stacked plan)."""
        members = ensemble.members
        mesh = self._mesh()
        steps = ensemble.steps
        acts = _act_schedule(ensemble.member_steps, steps, 1)  # (L, K, 1)
        ops4, t0_fns, step_fns = zip(*(
            self._member_step_fns(g, self.plan_for(g)[0]) for g in members))

        def t0_all(states, operands):
            return tuple(
                f(s, o) for f, s, o in zip(t0_fns, states, operands))

        def step_all(states, operands, t, act):  # act: (K, 1) replicated
            nxt = []
            for k, (s, o) in enumerate(zip(states, operands)):
                n = step_fns[k](s, o, t)
                nxt.append(jnp.where(act[k, 0] > 0, n, s))
            return tuple(nxt)

        sh = NamedSharding(mesh, P(AXIS))
        rep = NamedSharding(mesh, P())
        t0_jit = jax.jit(shard_map(
            t0_all, mesh=mesh, check_vma=False,
            in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS)))
        step_jit = jax.jit(shard_map(
            step_all, mesh=mesh, check_vma=False,
            in_specs=(P(AXIS), P(AXIS), P(), P()), out_specs=P(AXIS)))
        consts = tuple(
            tuple(jax.device_put(jnp.asarray(a), sh) for a in o) for o in ops4)
        admit_jits: dict = {}

        def init_fn(inits):
            return t0_jit(
                tuple(jax.device_put(x, sh) for x in inits), consts)

        def launch_fn(carry, act_row, t0):
            return step_jit(carry, consts, jnp.asarray(t0, jnp.int32),
                            jax.device_put(act_row, rep))

        def admit_fn(carry, slot, init):
            if slot not in admit_jits:
                f = t0_fns[slot]
                admit_jits[slot] = jax.jit(shard_map(
                    lambda s, o, f=f: f(s, o), mesh=mesh, check_vma=False,
                    in_specs=(P(AXIS), P(AXIS)), out_specs=P(AXIS)))
            fresh = admit_jits[slot](jax.device_put(init, sh), consts[slot])
            out = list(carry)
            out[slot] = fresh
            return tuple(out)

        model = self._cost_model(members[0].payload)
        rows = sum(self._block(g) for g in members)
        return EnsembleLaunchPlan(
            steps_per_launch=1,
            member_steps=tuple(ensemble.member_steps),
            acts=acts,
            init_fn=init_fn,
            launch_fn=launch_fn,
            finalize=lambda carry: tuple(carry),
            admit_fn=admit_fn,
            expected_launch_us=_schedule.expected_launch_wall_us(
                rows=rows, steps_per_launch=1, model=model,
                impl=self._halo_impl()),
            kind="stepwise",
            compile_counter=step_jit._cache_size,
        )

    # ----------------------------------------------------------- accounting

    def dispatches_per_run(self, graph: TaskGraph) -> int:
        """Actual kernel launches: the t=0 body-only launch plus
        ceil((T-1)/S) blocked combine launches (S=1 degenerates to T).
        The (halo-plan) pipelined schedule splits every blocked launch
        into a boundary launch + an interior launch — TWO kernel launches
        per deep exchange; the accounting stays honest about it (hiding
        the exchange is bought with an extra, smaller, launch). Stride
        plans are per-step BY CONSTRUCTION — a butterfly graph with a
        blocked request only drops below T launches when the all-gather
        plan actually grants a depth (width under the cap, resolver says
        yes), exactly mirroring ``_schedule_for_graph``."""
        plan = self._schedule_for_graph(graph)
        L = self._launches(graph.steps, plan.steps_per_launch)
        if plan.kind == PLAN_HALO and self._pipeline_active(
                self._block(graph), plan.steps_per_launch,
                _patterns.halo_radius(graph), graph.payload):
            return 1 + 2 * (L - 1)
        return L

    def ensemble_dispatches_per_run(self, ensemble: GraphEnsemble) -> int:
        """Stacked ensembles batch all K members into each launch (the
        pipelined split costs 2 launches per blocked iteration — boundary,
        covering both sides of all K members, plus interior); the tuple
        fallback launches each member every scan iteration (frozen members
        included — the kernel runs, the mask discards), so it pays the
        per-member count summed over members."""
        S = self._ensemble_steps_per_launch(ensemble)
        launches = self._launches(ensemble.steps, S)
        members = ensemble.members
        if self._is_stacked(ensemble):
            H = max(_patterns.halo_radius(g) for g in members)
            if self._pipeline_active(self._block(members[0]), S, H,
                                     members[0].payload):
                return 1 + 2 * (launches - 1)
            return launches
        total = 0
        for g in members:
            piped = self._pipeline_active(
                self._block(g), S, _patterns.halo_radius(g), g.payload)
            total += 1 + (2 if piped else 1) * (launches - 1)
        return total


def _with_call_spans(fn: Callable, tracer) -> Callable:
    """``fn`` with its first call in a ``pallas_step.first_call`` layer
    span and every later call in ``pallas_step.call``; adds no sync."""
    first = True

    def run(init):
        nonlocal first
        if first:
            with layer_span(tracer, "pallas_step.first_call"):
                out = fn(init)
            first = False
            return out
        with layer_span(tracer, "pallas_step.call", category="dispatch"):
            return fn(init)

    return run


def _stack_operands(ops4):
    """Stack per-member (idx, wgt, idx0, wgt0) on a leading K axis, padding
    every member's slot dim to the group max (idx 0 / weight 0: a harmless
    self-or-row-0 gather at weight zero)."""

    def stack(j):
        dmax = max(o[j].shape[1] for o in ops4)
        return np.stack([
            np.pad(o[j], ((0, 0), (0, dmax - o[j].shape[1])))
            for o in ops4
        ])

    return stack(0), stack(1), stack(2), stack(3)
