"""`bsp` runtime — bulk-synchronous shard_map (the MPI analogue).

Points are block-distributed over the device mesh. Every timestep is one
synchronous superstep: exchange (collective), then compute — exactly MPI's
send/recv + compute structure in the paper's Task Bench MPI backend.

Two dispatch models:
  bsp        one host dispatch per timestep (Python loop), charging per-step
             launch overhead like an MPI rank's per-iteration progress loop.
  bsp_scan   the whole timestep loop inside one jit (lax.scan + lax.switch
             over the pattern period) — the "perfectly amortized" MPI bound.

Collective selection per pattern class (see patterns.py):
  halo       ring ppermute of r edge rows each way
  butterfly  XOR block collective_permute (stride >= block) or local shuffle
  global     all_to_all -> psum-mean; spread -> all_gather + arithmetic gather
"""
from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import patterns as _patterns
from repro.core.graph import GraphEnsemble, TaskGraph
from repro.core.runtimes import _halo
from repro.core.runtimes.base import Runtime, register
from repro.core.task_kernels import apply_kernel

AXIS = "shard"


class _BspBase(Runtime):
    """Shared machinery for bsp / bsp_scan / overlap."""

    def _mesh(self) -> Mesh:
        return Mesh(np.array(self.devices), (AXIS,))

    def _block(self, graph: TaskGraph) -> int:
        return graph.width // len(self.devices)

    def supports(self, graph: TaskGraph):
        D = len(self.devices)
        if graph.width % D != 0:
            return False, f"width {graph.width} not divisible by {D} devices"
        B = graph.width // D
        pat = graph.pattern
        if pat in _patterns.HALO_PATTERNS or pat == "random_nearest":
            r = _patterns.halo_radius(graph)
            if r > B:
                return False, f"halo radius {r} exceeds block {B} (multi-hop needed)"
            return True, ""
        if pat in _patterns.BUTTERFLY_PATTERNS:
            if D & (D - 1):
                return False, "butterfly patterns need power-of-two device count"
            return True, ""
        if pat in ("all_to_all", "spread", "trivial"):
            return True, ""
        return False, f"pattern {pat} unsupported by {self.name}"

    # ---------------------------------------------------------- step bodies

    def _make_halo_step(self, graph: TaskGraph, use_pallas: bool) -> Callable:
        r = _patterns.halo_radius(graph)
        B = self._block(graph)
        D = len(self.devices)
        combine = _halo.make_halo_combine(graph)
        spec = graph.kernel

        def step(local):  # (B, payload)
            d = jax.lax.axis_index(AXIS)
            p0 = d * B
            if r == 0:
                x = combine(local, B, p0)
            else:
                recv_l, recv_r = _halo.exchange_halos(local, r, D, AXIS)
                ext = jnp.concatenate([recv_l, local, recv_r], axis=0)
                x = combine(ext, B, p0)
            return apply_kernel(x, spec, use_pallas=use_pallas)

        return step

    def _make_butterfly_steps(self, graph: TaskGraph, use_pallas: bool) -> List[Callable]:
        """One step body per period slot k (pairing distance 2^k_eff)."""
        W, D = graph.width, len(self.devices)
        B = W // D
        spec = graph.kernel

        def make(stride: int) -> Callable:
            def step(local):
                if stride < B:  # partner within block: local row shuffle
                    j = jnp.arange(B)
                    partner = local[j ^ stride]
                else:  # partner block: XOR collective permute
                    bs = stride // B
                    perm = [(d, d ^ bs) for d in range(D)]
                    partner = jax.lax.ppermute(local, AXIS, perm)
                x = (local + partner) * 0.5
                return apply_kernel(x, spec, use_pallas=use_pallas)

            return step

        return [make(s) for s in _patterns.butterfly_slot_strides(graph)]

    def _make_global_step(self, graph: TaskGraph, use_pallas: bool) -> Callable:
        W, D = graph.width, len(self.devices)
        B = W // D
        spec = graph.kernel
        if graph.pattern == "all_to_all":

            def step(local, t):
                mean = jax.lax.psum(local.sum(axis=0), AXIS) / W
                x = jnp.broadcast_to(mean[None, :], local.shape)
                # psum output is shard-invariant; re-mark as varying so scan
                # carries keep a consistent VMA type under shard_map.
                x = jax.lax.pcast(x, AXIS, to="varying")
                return apply_kernel(x, spec, use_pallas=use_pallas)

            return step

        if graph.pattern == "spread":
            stride = max(1, W // graph.fanout)

            def step(local, t):
                full = jax.lax.all_gather(local, AXIS, axis=0, tiled=True)  # (W, P)
                d = jax.lax.axis_index(AXIS)
                p = d * B + jnp.arange(B)
                ids = (p[:, None] + jnp.arange(graph.fanout)[None, :] * stride
                       + (t - 1)) % W  # (B, fanout)
                x = full[ids].mean(axis=1)
                return apply_kernel(x, spec, use_pallas=use_pallas)

            return step

        if graph.pattern == "trivial":

            def step(local, t):
                return apply_kernel(local, spec, use_pallas=use_pallas)

            return step

        raise ValueError(graph.pattern)

    def _make_member_step(self, graph: TaskGraph, use_pallas: bool) -> Callable:
        """Uniform step(local, t) for one graph, period branching included.

        This is the building block both the fused-loop ensembles (bsp_scan /
        overlap carry a tuple of these in one scan) and the single-graph
        scan body share.
        """
        pat = graph.pattern
        if pat in _patterns.HALO_PATTERNS or pat == "random_nearest":
            body = self._make_halo_step(graph, use_pallas)
            return lambda local, t: body(local)
        if pat in _patterns.BUTTERFLY_PATTERNS:
            bodies = self._make_butterfly_steps(graph, use_pallas)
            if len(bodies) == 1:
                return lambda local, t: bodies[0](local)
            period = graph.period

            def step(local, t):
                slot = jax.lax.rem(t - 1, period)
                return jax.lax.switch(
                    slot, [lambda s, b=b: b(s) for b in bodies], local
                )

            return step
        return self._make_global_step(graph, use_pallas)

    def _check_vma(self) -> bool:
        # pallas_call has no replication rule, so bodies that launch Pallas
        # kernels (use_pallas=True) must disable VMA/replication checking;
        # pure-jnp bodies keep the trace-time safety net.
        return not bool(self.options.get("use_pallas", False))

    def _shard_map(self, mesh: Mesh, fn: Callable, n_in: int = 1) -> Callable:
        return shard_map(
            fn,
            mesh=mesh,
            check_vma=self._check_vma(),
            in_specs=tuple([P(AXIS)] * n_in) if n_in > 1 else P(AXIS),
            out_specs=P(AXIS),
        )

    def _shard_map_tuple(self, mesh: Mesh, fn: Callable, k: int) -> Callable:
        """shard_map over a function taking/returning a K-tuple of states."""
        return shard_map(
            fn,
            mesh=mesh,
            check_vma=self._check_vma(),
            in_specs=(tuple([P(AXIS)] * k),),
            out_specs=tuple([P(AXIS)] * k),
        )


@register
class BspRuntime(_BspBase):
    name = "bsp"

    def _build_stepper(self, graph: TaskGraph):
        """(kernel_only, pick, sharding): the per-dispatch pieces of one graph."""
        use_pallas = bool(self.options.get("use_pallas", False))
        donate = bool(self.options.get("donate", True))
        mesh = self._mesh()
        spec = graph.kernel
        pat = graph.pattern

        kernel_only = self._shard_map(
            mesh, lambda local: apply_kernel(local, spec, use_pallas=use_pallas)
        )
        kernel_only = jax.jit(kernel_only, donate_argnums=(0,) if donate else ())

        if pat in _patterns.HALO_PATTERNS or pat == "random_nearest":
            body = self._make_halo_step(graph, use_pallas)
            steps = [jax.jit(self._shard_map(mesh, body),
                             donate_argnums=(0,) if donate else ())]
            pick = lambda t: steps[0]
        elif pat in _patterns.BUTTERFLY_PATTERNS:
            bodies = self._make_butterfly_steps(graph, use_pallas)
            steps = [jax.jit(self._shard_map(mesh, b),
                             donate_argnums=(0,) if donate else ())
                     for b in bodies]
            period = graph.period
            pick = lambda t: steps[(t - 1) % period]
        else:  # global patterns take (local, t): t rides in replicated
            body = self._make_global_step(graph, use_pallas)
            stepped = jax.jit(
                shard_map(
                    body, mesh=mesh, check_vma=self._check_vma(),
                    in_specs=(P(AXIS), P()), out_specs=P(AXIS)
                ),
                donate_argnums=(0,) if donate else (),
            )

            def pick(t):
                return lambda s: stepped(s, jnp.int32(t))

        return kernel_only, pick, NamedSharding(mesh, P(AXIS))

    def build(self, graph: TaskGraph) -> Callable[[jax.Array], jax.Array]:
        kernel_only, pick, sharding = self._build_stepper(graph)

        def run(init):
            state = kernel_only(jax.device_put(init, sharding))
            for t in range(1, graph.steps):
                state = pick(t)(state)
            return state

        return run

    def build_ensemble(self, ensemble: GraphEnsemble) -> Callable:
        """Round-robin host dispatch: per timestep, one dispatch per member,
        in member order. Models an MPI-style runtime: each member superstep
        is its own program, so no compiler may interleave one member's
        compute with another's exchange, and every superstep pays its own
        dispatch. (jax's async device queue may still pipeline adjacent
        dispatches; the denied freedom is compiler-level scheduling, which
        is what separates this rung from bsp_scan/overlap.)"""
        parts = [self._build_stepper(g) for g in ensemble.members]

        def run(inits):
            states = [
                ko(jax.device_put(x, sh))
                for (ko, _, sh), x in zip(parts, inits)
            ]
            for t in range(1, ensemble.steps):
                # members past their own T are frozen: no dispatch at all
                # (the host analogue of the fused backends' masked freeze)
                states = [
                    pick(t)(s) if t < g.steps else s
                    for (_, pick, _), s, g in zip(parts, states, ensemble.members)
                ]
            return tuple(states)

        return run

    def dispatches_per_run(self, graph: TaskGraph) -> int:
        return graph.steps

    def _build_traced(self, graph: TaskGraph) -> Callable:
        """Per-superstep spans: ``dispatch`` is the host call issuing the
        step program, ``compute.interior`` the wait for it to finish (the
        traced run blocks per step to obtain real intervals; the timed
        path keeps its async queue). The halo/stride collective runs
        INSIDE each superstep's program — MPI's exchange+compute rung is
        one dispatch by construction — so its wall lands in the compute
        span; per-transport attribution belongs to pallas_step's traced
        paths."""
        kernel_only, pick, sharding = self._build_stepper(graph)
        tr = self.tracer

        def run(init):
            with tr.span("t0_dispatch", "dispatch", step=0):
                state = kernel_only(jax.device_put(init, sharding))
            with tr.span("t0_compute", "compute.interior", step=0):
                state = jax.block_until_ready(state)
            for t in range(1, graph.steps):
                f = pick(t)
                with tr.span("superstep_dispatch", "dispatch", step=t):
                    state = f(state)
                with tr.span("superstep", "compute.interior", step=t,
                             pattern=graph.pattern):
                    state = jax.block_until_ready(state)
            return state

        return run


@register
class BspScanRuntime(_BspBase):
    """BSP with the timestep loop fused into the jit (amortized dispatch)."""

    name = "bsp_scan"

    def build(self, graph: TaskGraph) -> Callable[[jax.Array], jax.Array]:
        use_pallas = bool(self.options.get("use_pallas", False))
        unroll = int(self.options.get("unroll", 1))
        mesh = self._mesh()
        spec = graph.kernel
        step = self._make_member_step(graph, use_pallas)

        def local_run(local):  # (B, payload) per device
            local = apply_kernel(local, spec, use_pallas=use_pallas)
            if graph.steps == 1:
                return local

            def scan_body(state, t):
                return step(state, t), None

            local, _ = jax.lax.scan(
                scan_body, local, jnp.arange(1, graph.steps), unroll=unroll
            )
            return local

        fn = jax.jit(self._shard_map(mesh, local_run))
        sharding = NamedSharding(mesh, P(AXIS))
        return lambda init: fn(jax.device_put(init, sharding))

    def build_ensemble(self, ensemble: GraphEnsemble) -> Callable:
        """All members advance inside ONE jitted scan (tuple carry): a
        single host dispatch runs the whole ensemble, and XLA may interleave
        member supersteps — the amortized-dispatch MPI bound with full
        cross-member freedom."""
        use_pallas = bool(self.options.get("use_pallas", False))
        unroll = int(self.options.get("unroll", 1))
        mesh = self._mesh()
        members = ensemble.members
        specs = [g.kernel for g in members]
        steps = ensemble.steps
        member_steps = [self._make_member_step(g, use_pallas) for g in members]

        def local_run(locals_):  # tuple of (B_k, payload_k) per device
            locals_ = tuple(
                apply_kernel(x, sp, use_pallas=use_pallas)
                for x, sp in zip(locals_, specs)
            )
            if ensemble.steps == 1:
                return locals_

            def scan_body(states, t):
                nxt = []
                for g, st, s in zip(members, member_steps, states):
                    n = st(s, t)
                    if g.steps < steps:  # masked freeze past this member's T
                        n = jnp.where(t < g.steps, n, s)
                    nxt.append(n)
                return tuple(nxt), None

            locals_, _ = jax.lax.scan(
                scan_body, locals_, jnp.arange(1, ensemble.steps), unroll=unroll
            )
            return locals_

        fn = jax.jit(self._shard_map_tuple(mesh, local_run, len(members)))
        sharding = NamedSharding(mesh, P(AXIS))
        return lambda inits: fn(tuple(jax.device_put(x, sharding) for x in inits))

    def dispatches_per_run(self, graph: TaskGraph) -> int:
        return 1

    def ensemble_dispatches_per_run(self, ensemble: GraphEnsemble) -> int:
        return 1
