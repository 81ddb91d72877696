"""Runtime backend ABC + timing harness.

A *runtime* executes a TaskGraph. Each backend models one of the paper's
systems-under-test (DESIGN.md §2 has the full mapping):

  fused       whole-graph single jit + lax.scan      (OpenMP / static analogue)
  serialized  one host dispatch per task             (per-task spawn overhead)
  bsp         shard_map + per-step host dispatch     (MPI analogue)
  bsp_scan    shard_map + in-jit timestep loop       (MPI, amortized dispatch)
  overlap     overdecomposed, halo/compute overlap   (Charm++ / HPX analogue)

All backends must produce *identical* final states for the same graph — the
dataflow semantics live in task_kernels.combine_* and are shared. Tests
enforce cross-backend allclose; this is the system's core invariant.
"""
from __future__ import annotations

import abc
import dataclasses
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.graph import GraphEnsemble, TaskGraph
from repro.core.metg import GrainSample, combine_grain_samples
from repro.obs import coerce_tracer


def _fresh(x: jax.Array) -> jax.Array:
    """A copy safe to hand to a donating executable."""
    import jax.numpy as jnp

    return jnp.array(x, copy=True)


@dataclasses.dataclass(frozen=True)
class TimingStats:
    best: float
    mean: float
    walls: Tuple[float, ...]
    dispatches: int  # host->device dispatch count for one graph execution


@dataclasses.dataclass
class EnsembleLaunchPlan:
    """A host-steppable launch schedule for one ensemble run.

    The resilience engine (repro.resilience.engine) needs host visibility
    at launch boundaries — faults cannot be detected, retried, or replayed
    inside one opaque XLA program — so runtimes that can expose their
    launch structure build one of these instead of a single fused
    executor. Every launch_fn call is a pure, deterministic function of
    (carry, act row): replaying it from the pre-launch carry snapshot is
    bit-identical, which is the recovery guarantee the chaos suite locks
    in.

    ``acts`` is the host (L, K, S) activity schedule (the PR 3 act-mask
    machinery); the engine EDITS its own copy to evict a failed member
    (zero the (K, S) slot from the eviction launch on) or re-admit a
    fresh one into a freed slot.
    """

    #: lockstep timesteps advanced per launch (the blocked cadence)
    steps_per_launch: int
    #: each member's own horizon T_k (eviction reports freeze points
    #: against these)
    member_steps: Tuple[int, ...]
    #: (L, K, S) float32 per-depth activity masks, host-side
    acts: np.ndarray
    #: per-member initial states (sequence) -> device carry (the t=0
    #: body-only launch)
    init_fn: Callable[[Sequence[jax.Array]], Any]
    #: (carry, act_row (K, S) device array, t0 int32 scalar array) ->
    #: next carry; t0 is the launch's first lockstep timestep (ignored by
    #: schedules with time-invariant tables)
    launch_fn: Callable[[Any, jax.Array, jax.Array], Any]
    #: carry -> tuple of per-member (W_k, P_k) final states
    finalize: Callable[[Any], Tuple[jax.Array, ...]]
    #: (carry, slot, init state) -> carry with the slot's rows replaced by
    #: the fresh member's post-t0 state (re-admission); None when the
    #: schedule cannot replace rows in place
    admit_fn: Optional[Callable[[Any, int, jax.Array], Any]] = None
    #: measured-model expected per-launch wall (deadline basis); None when
    #: the cost model cannot price absolute walls
    expected_launch_us: Optional[float] = None
    #: descriptive schedule kind ("stacked" / "stepwise")
    kind: str = ""
    #: zero-arg callable reporting the launch executable's compile-cache
    #: entry count (the launch jit's ``_cache_size``);
    #: the serving fabric asserts it stays flat across membership churn —
    #: the no-recompile contract of act-mask evict/admit. None when the
    #: schedule cannot count compiles.
    compile_counter: Optional[Callable[[], int]] = None

    @property
    def num_launches(self) -> int:
        return int(self.acts.shape[0])

    def launch_t0(self, launch: int) -> int:
        """First lockstep timestep executed by launch ``launch``."""
        return 1 + launch * self.steps_per_launch


class Runtime(abc.ABC):
    """Executes task graphs under one scheduling/communication strategy."""

    #: registry name; subclasses set this
    name: str = "abstract"

    def __init__(self, devices: Optional[Sequence[jax.Device]] = None, **options):
        self.devices = list(devices) if devices is not None else jax.devices()
        self.options = options
        #: span recorder for `trace_once` and the layer spans (the
        #: ``trace=`` option; defaults to the shared NULL_TRACER, so with
        #: tracing off no span is recorded on any path)
        self.tracer = coerce_tracer(options.get("trace"))

    # -- capabilities ------------------------------------------------------

    def supports(self, graph: TaskGraph) -> Tuple[bool, str]:
        """Whether this backend can run the graph (and why not, if not)."""
        return True, ""

    def supports_ensemble(self, ensemble: GraphEnsemble) -> Tuple[bool, str]:
        """Whether this backend can run every member of the ensemble."""
        for i, g in enumerate(ensemble.members):
            ok, why = self.supports(g)
            if not ok:
                return False, f"member {i} ({g.describe()}): {why}"
        return True, ""

    def _require_support(self, graph: TaskGraph) -> None:
        ok, why = self.supports(graph)
        if not ok:
            raise ValueError(f"runtime {self.name} cannot run {graph.describe()}: {why}")

    def _require_ensemble_support(self, ensemble: GraphEnsemble) -> None:
        ok, why = self.supports_ensemble(ensemble)
        if not ok:
            raise ValueError(f"runtime {self.name} cannot run ensemble: {why}")

    # -- execution ---------------------------------------------------------

    @abc.abstractmethod
    def build(self, graph: TaskGraph) -> Callable[[jax.Array], Any]:
        """Compile an executor: initial (W, payload) state -> final state."""

    @abc.abstractmethod
    def build_ensemble(
        self, ensemble: GraphEnsemble
    ) -> Callable[[Tuple[jax.Array, ...]], Tuple[jax.Array, ...]]:
        """Compile a concurrent executor for K independent member graphs.

        Takes / returns one (W_k, payload_k) state per member. Member
        dataflows never mix; the backend only decides how much cross-member
        scheduling freedom exists (see GraphEnsemble docstring).
        """

    def dispatches_per_run(self, graph: TaskGraph) -> int:
        """Host->device dispatch count for one execution (overhead model)."""
        return 1

    def ensemble_dispatches_per_run(self, ensemble: GraphEnsemble) -> int:
        """Dispatch count for one ensemble execution.

        Round-robin backends pay every member's dispatches; single-program
        backends override this to 1.
        """
        return sum(self.dispatches_per_run(g) for g in ensemble.members)

    def _ensemble_inits(self, ensemble: GraphEnsemble) -> Tuple[jax.Array, ...]:
        from repro.core.task_kernels import initial_state

        return tuple(
            initial_state(g.width, g.payload, g.seed) for g in ensemble.members
        )

    def execute(self, graph: TaskGraph, init: Optional[jax.Array] = None) -> np.ndarray:
        """Run the graph once, returning the final (width, payload) state."""
        from repro.core.task_kernels import initial_state

        self._require_support(graph)
        if init is None:
            init = initial_state(graph.width, graph.payload, graph.seed)
        fn = self.build(graph)
        out = fn(_fresh(init))
        return np.asarray(jax.block_until_ready(out))

    def execute_ensemble(
        self,
        ensemble: GraphEnsemble,
        inits: Optional[Sequence[jax.Array]] = None,
    ) -> Tuple[np.ndarray, ...]:
        """Run all members concurrently; returns each member's final state."""
        self._require_ensemble_support(ensemble)
        if inits is None:
            inits = self._ensemble_inits(ensemble)
        elif len(inits) != len(ensemble.members):
            raise ValueError(
                f"got {len(inits)} initial states for "
                f"{len(ensemble.members)} ensemble members"
            )
        fn = self.build_ensemble(ensemble)
        outs = fn(tuple(_fresh(x) for x in inits))
        outs = jax.block_until_ready(outs)
        return tuple(np.asarray(o) for o in outs)

    # -- resilience --------------------------------------------------------

    def build_ensemble_launches(
        self, ensemble: GraphEnsemble
    ) -> EnsembleLaunchPlan:
        """A host-steppable launch schedule for resilient execution.

        Backends whose whole run is one opaque XLA program cannot expose
        launch boundaries — fault recovery for them is whole-run restart
        (checkpoint/elastic.py). pallas_step overrides this with its real
        blocked-launch structure.
        """
        raise NotImplementedError(
            f"runtime {self.name} has no launch-granular schedule; "
            f"resilient execution needs pallas_step (or whole-run restart "
            f"via checkpoint.elastic.run_with_restarts)")

    def execute_ensemble_resilient(
        self,
        ensemble: GraphEnsemble,
        *,
        plan=None,
        policy=None,
    ):
        """Run the ensemble under the resilience engine.

        ``plan`` is a repro.resilience FaultPlan (None = no injection; the
        engine's per-launch hook is a single predicate check, so the
        no-fault path adds no work beyond the host-stepped dispatch).
        Returns a repro.resilience.ResilientResult whose ``outputs`` match
        ``execute_ensemble``.
        """
        from repro.resilience import run_resilient

        self._require_ensemble_support(ensemble)
        return run_resilient(self, ensemble, plan=plan, policy=policy)

    # -- tracing -----------------------------------------------------------

    def _build_traced(self, graph: TaskGraph) -> Callable[[jax.Array], Any]:
        """An executor that records spans into ``self.tracer`` as it runs.

        Default (fused / bsp_scan / overlap / pallas_step — backends whose
        whole loop lives in one jit, opaque to host-side tracing): two
        run-level spans — ``dispatch`` is the host call issuing the
        program(s), ``compute.interior`` the wait for the device to drain;
        any layer spans the built program records nest inside them.
        Backends with real host boundaries (bsp, serialized) override this
        with per-step spans.
        """
        fn = self.build(graph)
        tr = self.tracer
        dispatches = self.dispatches_per_run(graph)

        def run(arg):
            with tr.span("run_dispatch", "dispatch", runtime=self.name,
                         dispatches=dispatches):
                out = fn(arg)
            with tr.span("device_drain", "compute.interior",
                         runtime=self.name):
                out = jax.block_until_ready(out)
            return out

        return run

    def trace_once(self, graph: TaskGraph,
                   init: Optional[jax.Array] = None) -> np.ndarray:
        """Run the graph once recording spans (a SEPARATE execution from
        `measure`, whose timed path records at most its layer spans). The
        traced executor is warmed up first and the warmup's spans dropped,
        so compile time never pollutes the attribution; build-time
        decision records and build spans (non-wall) survive. With the null
        tracer this is just `execute`."""
        tr = self.tracer
        if not tr.enabled:
            return self.execute(graph, init)
        from repro.core.task_kernels import initial_state

        self._require_support(graph)
        if init is None:
            init = initial_state(graph.width, graph.payload, graph.seed)
        init = jax.block_until_ready(jax.device_put(init))
        fn = self._build_traced(graph)
        mark = len(tr.spans)
        jax.block_until_ready(fn(_fresh(init)))  # compile warmup
        del tr.spans[mark:]
        out = fn(_fresh(init))
        return np.asarray(jax.block_until_ready(out))

    # -- measurement -------------------------------------------------------

    def measure(
        self,
        graph: TaskGraph,
        *,
        reps: int = 3,
        warmup: int = 1,
        init: Optional[jax.Array] = None,
    ) -> Tuple[GrainSample, TimingStats]:
        """Timed execution -> a GrainSample for the METG machinery."""
        from repro.core.task_kernels import initial_state

        self._require_support(graph)
        if init is None:
            init = initial_state(graph.width, graph.payload, graph.seed)
        init = jax.block_until_ready(jax.device_put(init))
        fn = self.build(graph)

        # backends may donate their input buffers; each invocation gets a
        # fresh copy, made OUTSIDE the timed region
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(fn(_fresh(init)))
        walls: List[float] = []
        for _ in range(reps):
            arg = jax.block_until_ready(_fresh(init))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            walls.append(time.perf_counter() - t0)

        stats = TimingStats(
            best=min(walls),
            mean=sum(walls) / len(walls),
            walls=tuple(walls),
            dispatches=self.dispatches_per_run(graph),
        )
        sample = GrainSample(
            iterations=graph.kernel.iterations,
            wall_time=stats.best,
            total_flops=float(graph.total_flops()),
            num_tasks=graph.num_tasks,
            cores=len(self.devices),
        )
        return sample, stats

    def measure_ensemble(
        self,
        ensemble: GraphEnsemble,
        *,
        reps: int = 3,
        warmup: int = 1,
    ) -> Tuple[GrainSample, TimingStats]:
        """Timed concurrent execution of all members -> one aggregate sample.

        The aggregate GrainSample (see metg.combine_grain_samples) sums
        FLOPs/tasks across members against the single measured ensemble
        wall, so `compute_metg` works unchanged on ensemble sweeps.
        """
        self._require_ensemble_support(ensemble)
        inits = tuple(
            jax.block_until_ready(jax.device_put(x))
            for x in self._ensemble_inits(ensemble)
        )
        fn = self.build_ensemble(ensemble)
        for _ in range(max(warmup, 1)):
            jax.block_until_ready(fn(tuple(_fresh(x) for x in inits)))
        walls: List[float] = []
        for _ in range(reps):
            args = jax.block_until_ready(tuple(_fresh(x) for x in inits))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(args))
            walls.append(time.perf_counter() - t0)

        stats = TimingStats(
            best=min(walls),
            mean=sum(walls) / len(walls),
            walls=tuple(walls),
            dispatches=self.ensemble_dispatches_per_run(ensemble),
        )
        members = [
            GrainSample(
                iterations=g.kernel.iterations,
                wall_time=stats.best,
                total_flops=float(g.total_flops()),
                num_tasks=g.num_tasks,
                cores=len(self.devices),
            )
            for g in ensemble.members
        ]
        return combine_grain_samples(members, wall_time=stats.best), stats

    def measure_launch_plan(
        self,
        ensemble: GraphEnsemble,
        *,
        reps: int = 3,
        warmup: int = 1,
    ) -> Tuple[GrainSample, TimingStats]:
        """Timed host-stepped execution of ``build_ensemble_launches``.

        One dispatch + host sync per launch — the cadence of the
        resilience engine and the serving loop, where a per-launch
        collective is paid at every host boundary instead of amortizing
        inside one scanned program. Transport choices that only differ
        in per-dispatch cost (gather impls, async halo transports) are
        invisible to `measure`'s fused executor and measurable here.
        """
        import jax.numpy as jnp

        self._require_ensemble_support(ensemble)
        lp = self.build_ensemble_launches(ensemble)
        inits = tuple(
            jax.block_until_ready(jax.device_put(x))
            for x in self._ensemble_inits(ensemble)
        )
        acts = np.asarray(lp.acts, dtype=np.float32)
        t0s = [jnp.asarray(lp.launch_t0(l), jnp.int32)
               for l in range(lp.num_launches)]

        def run_once():
            carry = jax.block_until_ready(
                lp.init_fn(tuple(_fresh(x) for x in inits)))
            for l in range(lp.num_launches):
                carry = jax.block_until_ready(
                    lp.launch_fn(carry, acts[l], t0s[l]))
            return lp.finalize(carry)

        for _ in range(max(warmup, 1)):
            jax.block_until_ready(run_once())
        walls: List[float] = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run_once())
            walls.append(time.perf_counter() - t0)

        stats = TimingStats(
            best=min(walls),
            mean=sum(walls) / len(walls),
            walls=tuple(walls),
            dispatches=1 + lp.num_launches,
        )
        members = [
            GrainSample(
                iterations=g.kernel.iterations,
                wall_time=stats.best,
                total_flops=float(g.total_flops()),
                num_tasks=g.num_tasks,
                cores=len(self.devices),
            )
            for g in ensemble.members
        ]
        return combine_grain_samples(members, wall_time=stats.best), stats


# ----------------------------------------------------------------- registry

_REGISTRY: dict = {}


def register(cls):
    _REGISTRY[cls.name] = cls
    return cls


def get_runtime(name: str, **kwargs) -> Runtime:
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown runtime {name!r}; known: {sorted(_REGISTRY)}") from None
    return cls(**kwargs)


def available_runtimes() -> List[str]:
    return sorted(_REGISTRY)
