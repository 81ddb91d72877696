"""Shared halo-exchange dataflow used by the distributed runtimes.

Points are block-distributed: device d owns rows [d*B, (d+1)*B) of the global
(W, payload) state. Halo-expressible patterns (stencil/dom/nearest/...) reach
at most ``r = halo_radius`` points across, so one ring exchange of r edge rows
per direction supplies all remote inputs.

``make_halo_combine`` builds a combine closure that EXACTLY matches
``task_kernels.combine_dependencies`` (mean over live deps) so fused and
distributed backends stay bit-compatible — the masks below must mirror
patterns.dependencies for every edge case (global edges, dom's asymmetry,
random_nearest's keep set).

Async interface (the pipelined `pallas_step` path): ``exchange_halos_start``
/ ``exchange_edges_start`` issue the ring transfer and return a
``HaloHandle``; ``exchange_halos_join`` yields the received rows. The
default (and only off-TPU) implementation issues ``ppermute`` ops whose
results nothing touches until the join point — the asynchrony is the SSA
dataflow itself: XLA's latency-hiding scheduler splits the collective into
start/done thunks and runs any independent compute between issue and join
under the transfer. On TPU, a Mosaic ``make_async_remote_copy`` ring kernel
(double-buffered VMEM halo slots, send/recv semaphores per direction) can
slot in behind the same start/join interface; it is not implemented here
because this container cannot lower or validate it — the interface is the
contract, `HALO_ASYNC_IMPLS` the registry a TPU build extends.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import patterns as _patterns
from repro.core.graph import TaskGraph


def offset_keep(graph: TaskGraph) -> np.ndarray:
    """Which window offsets [-r..r] the pattern actually consumes."""
    r = _patterns.halo_radius(graph)
    offsets = np.arange(-r, r + 1)
    if graph.pattern == "no_comm":
        return offsets == 0
    if graph.pattern == "dom":
        return offsets <= 0
    # stencil_1d(_periodic), nearest, random_nearest: whole window
    return np.ones_like(offsets, dtype=bool)


def random_keep_table(graph: TaskGraph) -> Optional[np.ndarray]:
    """(W, 2r+1) keep mask for random_nearest; None for other patterns."""
    if graph.pattern != "random_nearest":
        return None
    r = graph.radius
    W = graph.width
    keep = np.zeros((W, 2 * r + 1), dtype=np.float32)
    for p in range(W):
        deps = set(_patterns.dependencies(graph, 1, p))
        for j, o in enumerate(range(-r, r + 1)):
            if (p + o) % W in deps:
                keep[p, j] = 1.0
    return keep


def make_halo_combine(graph: TaskGraph) -> Callable:
    """Build combine(ctx, n, p0) -> (n, payload).

    Args (of the returned closure):
      ctx: (n + 2r, payload) rows giving each output row its full window:
           output row i consumes ctx rows [i, i + 2r].
      n:   static number of output rows.
      p0:  traced global point id of output row 0 (for edge masking).
    """
    r = _patterns.halo_radius(graph)
    if r < 0:
        raise ValueError(f"{graph.pattern} is not halo-expressible")
    keep_np = offset_keep(graph)
    nonperiodic = graph.pattern in ("stencil_1d", "dom")
    rand_np = random_keep_table(graph)
    W = graph.width
    rand = jnp.asarray(rand_np) if rand_np is not None else None

    def combine(ctx: jax.Array, n: int, p0: jax.Array) -> jax.Array:
        if r == 0:  # no_comm: self only
            return ctx
        windows = jnp.stack(
            [
                jax.lax.dynamic_slice_in_dim(ctx, j, n, axis=0)
                for j in range(2 * r + 1)
            ],
            axis=1,
        )  # (n, 2r+1, payload)
        p = p0 + jnp.arange(n)  # (n,) global ids
        offs = jnp.arange(-r, r + 1)  # (2r+1,)
        mask = jnp.broadcast_to(
            jnp.asarray(keep_np, jnp.float32)[None, :], (n, 2 * r + 1)
        )
        if nonperiodic:
            q = p[:, None] + offs[None, :]
            mask = mask * ((q >= 0) & (q < W)).astype(jnp.float32)
        if rand is not None:
            mask = mask * jax.lax.dynamic_slice_in_dim(rand, p0, n, axis=0)
        denom = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
        return (windows * mask[..., None]).sum(axis=1) / denom

    return combine


def ring_perms(num_devices: int, axis: str = "shard"):
    """Forward (d -> d+1) and backward (d -> d-1) ring permutations."""
    fwd = [(d, (d + 1) % num_devices) for d in range(num_devices)]
    bwd = [(d, (d - 1) % num_devices) for d in range(num_devices)]
    return fwd, bwd


@dataclasses.dataclass(frozen=True)
class HaloHandle:
    """An in-flight ring exchange: the double-buffered halo slots.

    ``recv_left``/``recv_right`` are the transfer's landing buffers. Under
    the XLA implementation they are ordinary traced arrays that no op may
    consume before ``exchange_halos_join`` — keeping the window between
    start and join free of data dependences is what lets the scheduler run
    the collective under unrelated compute. A Mosaic implementation would
    carry (buffer, semaphore) pairs here instead; only the join may touch
    the buffers in either case.
    """

    recv_left: jax.Array
    recv_right: jax.Array

    def join(self) -> Tuple[jax.Array, jax.Array]:
        return self.recv_left, self.recv_right


def _gather_edges_start(first: jax.Array, last: jax.Array, num_devices: int,
                        axis: str = "shard", *, row_axis: int = 0) -> HaloHandle:
    """Fused default: ONE collective moves both directions.

    Having both edge buffers in hand at issue time — the property the
    double-buffered interface guarantees — lets the two ring directions
    share a single all-gather of the packed [first | last] edges instead of
    paying one collective rendezvous per direction (two back-to-back
    ppermutes cost ~3x one collective on this container's forced-host
    devices). Each device then slices its left neighbor's ``last`` and
    right neighbor's ``first`` out of the gathered ring locally; the moved
    rows are exact copies either way, so transports are bit-identical.
    """
    r = first.shape[row_axis]
    packed = jnp.concatenate([first, last], axis=row_axis)  # (2r, ...)
    ring = jax.lax.all_gather(
        packed, axis, axis=row_axis, tiled=True)  # (D * 2r, ...)
    d = jax.lax.axis_index(axis)
    left = jnp.mod(d - 1, num_devices) * 2 * r + r   # d-1's `last` rows
    right = jnp.mod(d + 1, num_devices) * 2 * r      # d+1's `first` rows
    return HaloHandle(
        recv_left=jax.lax.dynamic_slice_in_dim(ring, left, r, axis=row_axis),
        recv_right=jax.lax.dynamic_slice_in_dim(ring, right, r, axis=row_axis),
    )


def _ppermute_edges_start(first: jax.Array, last: jax.Array, num_devices: int,
                          axis: str = "shard", *, row_axis: int = 0) -> HaloHandle:
    """ppermute variant: one collective per direction, results untouched
    until the join — the transport ``exchange_halos`` uses, kept for
    parity testing and as the donated-buffer fallback where an all-gather
    does not lower."""
    del row_axis  # ppermute moves whole buffers; the slicing already happened
    fwd, bwd = ring_perms(num_devices, axis)
    return HaloHandle(
        recv_left=jax.lax.ppermute(last, axis, fwd),   # from d-1: its last r
        recv_right=jax.lax.ppermute(first, axis, bwd),  # from d+1: its first r
    )


#: name -> edge-transfer starter. "xla" (the fused single-collective
#: transport) is the portable default, "ppermute" the per-direction
#: variant; a TPU build registers "mosaic" (make_async_remote_copy ring
#: kernel) under the same signature and everything above this module is
#: unchanged.
HALO_ASYNC_IMPLS = {
    "xla": _gather_edges_start,
    "ppermute": _ppermute_edges_start,
}


def exchange_edges_start(first: jax.Array, last: jax.Array, num_devices: int,
                         axis: str = "shard", *, row_axis: int = 0,
                         impl: str = "xla") -> HaloHandle:
    """Start a ring exchange of PRE-SLICED edge rows (``r <= block``).

    ``first``/``last`` are this device's leading/trailing r rows (along
    ``row_axis``) — e.g. the boundary-phase outputs of a pipelined launch,
    which are exactly the rows the next launch's neighbors need, so the
    transfer can be issued the moment they exist, before any interior
    compute. Join with ``exchange_halos_join``.
    """
    try:
        start = HALO_ASYNC_IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"unknown halo async impl {impl!r}; known {sorted(HALO_ASYNC_IMPLS)}"
        ) from None
    return start(first, last, num_devices, axis, row_axis=row_axis)


def exchange_halos_start(local: jax.Array, r: int, num_devices: int,
                         axis: str = "shard", *, row_axis: int = 0,
                         impl: str = "xla") -> HaloHandle:
    """Start a ring exchange of r edge rows each way; join for the results.

    The async counterpart of ``exchange_halos`` (same depth semantics,
    including the multi-hop deep path): slices the edge rows and issues the
    transfers, returning a ``HaloHandle`` whose buffers must not be
    consumed before ``exchange_halos_join``. Multi-hop depths (``r >
    block``) issue the whole chain of block shifts up front; the chain is
    still one dependence-free island the scheduler may sink under
    independent compute.
    """
    n = local.shape[row_axis]
    if r <= n:
        last = jax.lax.slice_in_dim(local, n - r, n, axis=row_axis)
        first = jax.lax.slice_in_dim(local, 0, r, axis=row_axis)
        return exchange_edges_start(first, last, num_devices, axis,
                                    row_axis=row_axis, impl=impl)

    fwd, bwd = ring_perms(num_devices, axis)
    hops = -(-r // n)  # ceil: whole-block shifts per direction
    left_blocks = []   # hop h holds block d-h: collect nearest-first
    right_blocks = []  # hop h holds block d+h
    cur_l = cur_r = local
    for _ in range(hops):
        cur_l = jax.lax.ppermute(cur_l, axis, fwd)
        cur_r = jax.lax.ppermute(cur_r, axis, bwd)
        left_blocks.append(cur_l)
        right_blocks.append(cur_r)
    # global row order: [d-hops .. d-1] on the left, [d+1 .. d+hops] right
    left_full = jnp.concatenate(list(reversed(left_blocks)), axis=row_axis)
    right_full = jnp.concatenate(right_blocks, axis=row_axis)
    total = hops * n
    recv_left = jax.lax.slice_in_dim(
        left_full, total - r, total, axis=row_axis)
    recv_right = jax.lax.slice_in_dim(right_full, 0, r, axis=row_axis)
    return HaloHandle(recv_left=recv_left, recv_right=recv_right)


def exchange_halos_join(handle: HaloHandle) -> Tuple[jax.Array, jax.Array]:
    """Complete an exchange: (recv_left, recv_right), now safe to consume."""
    return handle.join()


# --------------------------------------------------------------- strides
#
# Butterfly patterns (fft/tree) pair point p with p XOR 2^k — at block
# strides, device d's partner rows live wholesale on device d XOR bs
# (bs = stride // block). Unlike the ring halo there is no left/right:
# the XOR permutation is an involution, so ONE permute both sends and
# receives a full partner block per requested stride.


@dataclasses.dataclass(frozen=True)
class StrideHandle:
    """In-flight XOR block exchange: one landing buffer per stride.

    ``partners[j]`` is the full local-shaped block of device
    ``d XOR block_strides[j]``. The same start/join discipline as
    ``HaloHandle`` applies: nothing may consume a buffer before the join,
    which is what lets XLA's latency-hiding scheduler sink the
    collective(s) under independent compute. A Mosaic transport would
    carry (buffer, semaphore) pairs per stride behind the same interface.
    """

    partners: Tuple[jax.Array, ...]

    def join(self) -> Tuple[jax.Array, ...]:
        return self.partners


def _gather_stride_start(local: jax.Array, block_strides, num_devices: int,
                         axis: str = "shard", *,
                         row_axis: int = 0) -> StrideHandle:
    """Fused default: ONE all-gather serves every requested stride.

    Each device slices the blocks it needs — d XOR bs for each bs — out
    of the gathered ring locally. One collective rendezvous regardless of
    how many strides the caller wants (the same trade the fused halo
    transport makes: on forced-host devices rendezvous cost dominates
    moved bytes).
    """
    n = local.shape[row_axis]
    ring = jax.lax.all_gather(local, axis, axis=row_axis, tiled=True)
    d = jax.lax.axis_index(axis)
    return StrideHandle(partners=tuple(
        jax.lax.dynamic_slice_in_dim(
            ring, jnp.bitwise_xor(d, jnp.int32(bs)) * n, n, axis=row_axis)
        for bs in block_strides
    ))


def _ppermute_stride_start(local: jax.Array, block_strides, num_devices: int,
                           axis: str = "shard", *,
                           row_axis: int = 0) -> StrideHandle:
    """ppermute variant: one XOR collective per stride (moves only the
    partner blocks; kept for parity testing and as the minimal-traffic
    transport where an all-gather does not lower)."""
    del row_axis  # whole blocks move; no slicing needed
    partners = []
    for bs in block_strides:
        perm = [(d, d ^ int(bs)) for d in range(num_devices)]
        partners.append(jax.lax.ppermute(local, axis, perm))
    return StrideHandle(partners=tuple(partners))


#: name -> stride-transfer starter, mirroring HALO_ASYNC_IMPLS: "xla" is
#: the fused single-collective default, "ppermute" the per-stride variant;
#: a TPU build registers "mosaic" (make_async_remote_copy with one
#: send/recv semaphore pair per stride) under the same signature.
STRIDE_ASYNC_IMPLS = {
    "xla": _gather_stride_start,
    "ppermute": _ppermute_stride_start,
}

def _gather_xla(local: jax.Array, num_devices: int, axis: str,
                *, row_axis: int = 0) -> jax.Array:
    """One tiled all-gather: the monolithic baseline transport."""
    return jax.lax.all_gather(local, axis, axis=row_axis, tiled=True)


def _gather_ppermute(local: jax.Array, num_devices: int, axis: str,
                     *, row_axis: int = 0) -> jax.Array:
    """Assemble the ring from D-1 whole-block backward shifts and rotate
    into global order — the minimal-collective-primitive spelling, kept
    for transport parity tests (exact row copies, so outputs are
    bit-identical to "xla")."""
    _, bwd = ring_perms(num_devices, axis)
    blocks = [local]  # device-local order: [d, d+1, ..., d+D-1]
    cur = local
    for _ in range(num_devices - 1):
        cur = jax.lax.ppermute(cur, axis, bwd)
        blocks.append(cur)
    stacked = jnp.concatenate(blocks, axis=row_axis)
    n = local.shape[row_axis]
    d = jax.lax.axis_index(axis)
    # rotate [d..d+D-1] into [0..D-1]: global row 0 sits n*d rows from the
    # END of the device-local order exactly when d > 0; a doubled buffer
    # sliced at (D - d) * n mod (D * n) does it without traced-shift roll
    doubled = jnp.concatenate([stacked, stacked], axis=row_axis)
    start = jnp.mod((num_devices - d) * n, num_devices * n)
    return jax.lax.dynamic_slice_in_dim(
        doubled, start, num_devices * n, axis=row_axis)


def gather_chunk_group(num_devices: int) -> int:
    """Segment size for the chunked gather: the divisor of D nearest
    sqrt(D), so both stages rendezvous ~sqrt(D) participants instead of
    one D-wide barrier. 1 or D degenerates to the monolithic gather."""
    best, best_err = 1, float("inf")
    for g in range(1, num_devices + 1):
        if num_devices % g:
            continue
        err = abs(g - num_devices ** 0.5)
        if err < best_err or (err == best_err and g > best):
            best, best_err = g, err
    return best


def _gather_chunked(local: jax.Array, num_devices: int, axis: str,
                    *, row_axis: int = 0,
                    group: Optional[int] = None) -> jax.Array:
    """Hierarchical (neighbor-limited) gather: a ring of segment
    all-gathers instead of one D-wide rendezvous.

    Stage 1 all-gathers within contiguous ring segments of G devices;
    stage 2 all-gathers the assembled segment blocks across
    one-representative-per-segment stride groups. Each collective
    synchronizes a bounded participant count, which is what makes the
    global patterns pay O(W/D * log D)-ish coordination instead of a flat
    D-wide barrier per launch. Both stages move exact row copies in global
    order, so the result is bit-identical to the monolithic transport —
    for EVERY G | D, which is why G is a pure cost choice.

    ``group=None`` delegates G to the scheduling policy
    (``schedule.choose_gather_chunk_group``: explicit > env > measured
    grouping probes > the sqrt(D) analytic rule); an explicit ``group``
    must divide D. G <= 1 or G >= D degenerates to the monolithic gather.
    """
    if group is None:
        # lazy policy import (mirrors the runtime's schedule use): this
        # module must stay importable without the probes/cache machinery
        from repro.kernels import schedule as _schedule

        group, _ = _schedule.choose_gather_chunk_group(
            devices=num_devices,
            width=local.shape[row_axis] * num_devices)
    g = int(group)
    if g >= 1 and num_devices % g:
        raise ValueError(
            f"chunked gather group {g} does not divide D={num_devices}")
    if g <= 1 or g >= num_devices:
        return _gather_xla(local, num_devices, axis, row_axis=row_axis)
    ngroups = num_devices // g
    segments = [[b * g + i for i in range(g)] for b in range(ngroups)]
    seg = jax.lax.all_gather(local, axis, axis=row_axis, tiled=True,
                             axis_index_groups=segments)
    across = [[i + b * g for b in range(ngroups)] for i in range(g)]
    return jax.lax.all_gather(seg, axis, axis=row_axis, tiled=True,
                              axis_index_groups=across)


#: name -> global-gather transport, mirroring the halo/stride registries:
#: "xla" is the monolithic tiled all-gather, "ppermute" the D-1-shift ring
#: spelling, "chunked" the hierarchical two-stage segment gather that
#: bounds every rendezvous at ~sqrt(D) participants (the D >= 16 default
#: when a measured cost model ranks it cheaper).
GATHER_IMPLS = {
    "xla": _gather_xla,
    "ppermute": _gather_ppermute,
    "chunked": _gather_chunked,
}

#: kind -> the mutable transport registry behind it. This is the public
#: seam for transport extensions: a TPU build registers "mosaic" starters,
#: and the fault-injection layer (repro.resilience.faults) registers
#: "chaos+<base>" wrappers that delegate to the base impl but consult the
#: armed FaultPlan first — production impls and callers are untouched.
TRANSPORT_REGISTRIES = {
    "halo": HALO_ASYNC_IMPLS,
    "stride": STRIDE_ASYNC_IMPLS,
    "gather": GATHER_IMPLS,
}


def register_transport_impl(kind: str, name: str, start,
                            *, replace: bool = False) -> None:
    """Register a named transport starter in the ``kind`` registry.

    ``start`` must follow the registry's starter signature (see
    ``HALO_ASYNC_IMPLS`` / ``STRIDE_ASYNC_IMPLS``). Silent shadowing of a
    production transport is refused unless ``replace=True`` — a chaos
    wrapper accidentally registered as "xla" would corrupt every runtime
    in the process.
    """
    try:
        registry = TRANSPORT_REGISTRIES[kind]
    except KeyError:
        raise ValueError(
            f"unknown transport registry {kind!r}; "
            f"known {sorted(TRANSPORT_REGISTRIES)}") from None
    if name in registry and not replace:
        raise ValueError(
            f"transport impl {name!r} already registered for {kind!r}; "
            f"pass replace=True to shadow it deliberately")
    registry[name] = start


def exchange_stride_start(local: jax.Array, block_strides, num_devices: int,
                          axis: str = "shard", *, row_axis: int = 0,
                          impl: str = "xla") -> StrideHandle:
    """Start an XOR block exchange for each stride in ``block_strides``.

    ``num_devices`` must be a power of two (d XOR bs is only a
    permutation of the ring when it is; on other counts some partners
    fall off the mesh and the transports would diverge — ppermute crashes
    while the gather transport's clamped slice silently delivers wrong
    rows, so the contract is enforced loudly here). Every stride must be
    in [1, num_devices) (in-block pairing distances never reach this
    function — the caller shuffles locally). Join with
    ``exchange_stride_join``.
    """
    if num_devices & (num_devices - 1):
        raise ValueError(
            f"XOR stride exchange needs a power-of-two device count, "
            f"got {num_devices} (partner d XOR bs would leave the mesh)")
    for bs in block_strides:
        if not 0 < int(bs) < num_devices:
            raise ValueError(
                f"block stride {bs} outside [1, {num_devices}) — in-block "
                f"strides are local shuffles, not exchanges")
    try:
        start = STRIDE_ASYNC_IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"unknown stride async impl {impl!r}; "
            f"known {sorted(STRIDE_ASYNC_IMPLS)}"
        ) from None
    return start(local, tuple(int(b) for b in block_strides), num_devices,
                 axis, row_axis=row_axis)


def exchange_stride_join(handle: StrideHandle) -> Tuple[jax.Array, ...]:
    """Complete a stride exchange: the partner blocks, safe to consume."""
    return handle.join()


def exchange_stride(local: jax.Array, block_strides, num_devices: int,
                    axis: str = "shard", *, row_axis: int = 0,
                    impl: str = "xla") -> Tuple[jax.Array, ...]:
    """Synchronous spelling: start and join back-to-back."""
    return exchange_stride_join(
        exchange_stride_start(local, block_strides, num_devices, axis,
                              row_axis=row_axis, impl=impl))


def gather_global(local: jax.Array, num_devices: int, axis: str = "shard",
                  *, row_axis: int = 0, impl: str = "xla",
                  chunk_group: Optional[int] = None) -> jax.Array:
    """The full global-order state on every device (the all-gather plan).

    ``impl`` names a GATHER_IMPLS transport: "xla" (one monolithic tiled
    all-gather), "ppermute" (D-1 ring shifts, parity-test spelling), or
    "chunked" (hierarchical segment gather bounding every rendezvous at
    ~sqrt(D) participants). All transports move exact row copies, so
    outputs are bit-identical across impls. ``chunk_group`` forces the
    chunked transport's rendezvous group G (must divide D); it only
    reaches the plain "chunked" impl — registry wrappers such as
    "chaos+chunked" keep the policy-resolved default.
    """
    if num_devices == 1:
        return local
    try:
        start = GATHER_IMPLS[impl]
    except KeyError:
        raise ValueError(
            f"unknown gather impl {impl!r}; known "
            f"{sorted(GATHER_IMPLS)}") from None
    if chunk_group is not None and impl == "chunked":
        return start(local, num_devices, axis, row_axis=row_axis,
                     group=chunk_group)
    return start(local, num_devices, axis, row_axis=row_axis)


def global_mean(local: jax.Array, width: int, num_devices: int,
                axis: str = "shard", *, row_axis: int = 0) -> jax.Array:
    """Mean over the GLOBAL row axis via one psum — the uniform
    all_to_all combine lowering.

    When every point depends on every point with weight 1/W, the gathered
    W-row buffer collapses to one vector: sum the local rows, psum the
    partial across the row axis, divide by W. This replaces an O(W)
    replication per launch with an O(payload) reduction. NOT bit-identical
    to the gather+masked-mean kernel (different summation order), but
    within float32 reduction tolerance — callers gate it behind an option.
    """
    partial = jnp.sum(local, axis=row_axis)
    if num_devices > 1:
        partial = jax.lax.psum(partial, axis)
    return partial / jnp.asarray(width, local.dtype)


def exchange_halos(local: jax.Array, r: int, num_devices: int,
                   axis: str = "shard", *, row_axis: int = 0):
    """Ring-exchange r edge rows each way (multi-hop when r exceeds a block).

    Returns (recv_left, recv_right): the r rows that sit immediately
    left/right of this device's block in global order (wrapped at the ends;
    wrap values are masked off by the combine for non-periodic patterns).
    ``row_axis`` is the point-row dimension — 0 for a (B, payload) block, 1
    for an ensemble's stacked (K, B, payload) block, where one exchange
    moves every member's halos at once.

    ``r <= B`` is one ppermute of r sliced edge rows per direction (the
    per-step fast path). Deep halos (``r > B``, e.g. the temporal-blocked
    megakernel's S*radius rows) compose ``ceil(r / B)`` whole-block ring
    shifts per direction: hop h delivers the block h devices away, the
    blocks concatenate in global row order, and the innermost r rows are
    returned. Depths past a full ring wrap (hop count may exceed the device
    count) simply revisit blocks, which is exactly the periodic/mod-W
    semantics the halo combines expect.

    This is the synchronous spelling — start and join back-to-back, pinned
    to the established per-direction ppermute transport so every backend
    that predates the pipeline (bsp/bsp_scan/overlap, and pallas_step's
    serial schedule) keeps its measured behavior. The pipelined paths call
    start/join themselves to put compute between, and default to the fused
    single-collective transport instead.
    """
    return exchange_halos_join(
        exchange_halos_start(local, r, num_devices, axis, row_axis=row_axis,
                             impl="ppermute")
    )


def ring_extend(local: jax.Array, r: int, num_devices: int,
                axis: str = "shard", *, row_axis: int = 0) -> jax.Array:
    """``local`` extended by its r ring neighbours' rows on each side,
    [recv_left | local | recv_right] along ``row_axis`` (``exchange_halos``
    then one concatenate), under the ``halo_extend`` named scope so the
    ops keep that name in a profile."""
    with jax.named_scope("halo_extend"):
        rl, rr = exchange_halos(local, r, num_devices, axis,
                                row_axis=row_axis)
        return jnp.concatenate([rl, local, rr], axis=row_axis)
