"""The grain ladder: the paper's METG protocol on one Task Bench graph.

One TaskGraph is run at each rung of a grain ladder, in ascending grain
(paper sec. 6.1, Fig. 1). The window is shared equally among the rungs and
each rung runs whole graphs back to back, with about a quarter second of
work in flight, so the device does not wait for the host. A rung's rate is
all useful FLOPs of the graphs it completed over all of its time.

The system under test is what a user calls: ``get_runtime("pallas_step",
devices=...)`` with default options, ``.build(graph)``, and the built
program called on initial states. Set-up builds and warms every rung's
program and makes the initial states on the device from the seed, so
nothing compiles inside the window.

Correctness: the outputs of a sample of the window's graphs, drawn from the
seed, are compared after the window with the plain reference
(``bench/reference.py``) run on the same initial states. A compute_bound
body contracts every finite state towards its fixed point long before step
1000, so each initial state also carries a few non-finite values
(``nonfinite_sources`` in the configuration): they are fixed points of the
body and spread along the dependencies, so the output shows each one's
light cone, which a wrong combine, a missing exchange or a wrong step count
changes.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List

import numpy as np

from bench import reference
from bench.flops import task_flops
from bench.metg import Rung, metg_seconds


@dataclasses.dataclass
class Ladder:
    """What set-up made: one built program per rung and their inputs."""

    jax: object
    graphs: List[object]        # TaskGraph per rung
    programs: List[object]      # built program per rung
    sharding: object            # the programs' state sharding
    width: int
    chips: int
    inits_per_rung: int
    sources: int
    make_inits: object
    body: tuple                 # (a, b) of the compute_bound body
    dtype: object               # the configuration's state dtype
    graph_s: List[float] = dataclasses.field(default_factory=list)


def _seed_words(seed: int, salt: int) -> np.ndarray:
    return np.random.SeedSequence([seed, salt]).generate_state(2, np.uint32)


def _inits_maker(jax, shape, dtype):
    """One jitted call: (N, W, P) uniform states in [0.1, 1) with the
    non-finite sources written in."""
    jnp = jax.numpy

    @jax.jit
    def make(key_words, rows, cols, vals):
        key = jax.random.wrap_key_data(key_words)
        x = jax.random.uniform(key, shape, dtype, 0.1, 1.0)
        n = jnp.broadcast_to(jnp.arange(shape[0])[:, None], rows.shape)
        return x.at[n, rows, cols].set(vals)

    return make


def _draw(make, graph, width: int, sources: int, count: int, seed: int):
    """``count`` initial states from ``seed``, each with ``sources``
    non-finite values at least ``steps`` points from either edge, so no
    light cone reaches an edge."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    lo, hi = graph.steps, width - graph.steps
    if not (sources and lo < hi):
        sources = 0
    rows = rng.integers(lo, max(hi, lo + 1), size=(count, sources))
    cols = rng.integers(0, graph.payload, size=(count, sources))
    vals = rng.choice(np.array([np.inf, -np.inf, np.nan], np.float32),
                      size=(count, sources))
    return make(_seed_words(seed, 0), rows.astype(np.int32),
                cols.astype(np.int32), vals.astype(np.float32))


def _place(jax, x, sharding, rungs: int, per_rung: int):
    return [[jax.device_put(x[r * per_rung + k], sharding)
             for k in range(per_rung)] for r in range(rungs)]


def make_inits(lad: Ladder, seed: int) -> List[List[object]]:
    """Initial states per rung, made on the device from ``seed`` and placed
    as the programs hold their state."""
    R, K = len(lad.graphs), lad.inits_per_rung
    x = _draw(lad.make_inits, lad.graphs[0], lad.width, lad.sources, R * K,
              seed)
    return _place(lad.jax, x, lad.sharding, R, K)


def setup(ctx):
    """Build, compile and warm every rung's program and print its plan.
    Returns the ladder and the initial states of ``ctx.seed``."""
    jax = ctx.jax
    from repro.core.graph import TaskGraph
    from repro.core.runtimes.base import get_runtime
    from repro.core.task_kernels import KernelSpec

    cfg, traffic = ctx.config, ctx.traffic
    width = cfg["points_per_chip"] * ctx.chips
    rt = get_runtime(cfg["system"], devices=ctx.devices)
    graphs = [TaskGraph(steps=cfg["steps"], width=width,
                        pattern=cfg["pattern"], payload=cfg["payload"],
                        kernel=KernelSpec(cfg["kernel"], g))
              for g in traffic["grains"]]
    programs = []
    for g in graphs:
        plan = rt._schedule_for_graph(g)
        ctx.say(f"rung grain={g.kernel.iterations} plan={plan.kind} "
                f"S={plan.steps_per_launch} "
                f"launches={rt.dispatches_per_run(g)}")
        programs.append(rt.build(g))
    ctx.mark("build")
    R, K = len(graphs), traffic["inits_per_rung"]
    dtype = jax.numpy.dtype(cfg["dtype"])
    make = _inits_maker(jax, (R * K, width, cfg["payload"]), dtype)
    x = _draw(make, graphs[0], width, cfg["nonfinite_sources"], R * K,
              ctx.seed)
    # the first call of each program compiles; its output shows where the
    # program keeps its state, so the window's inputs can be put there
    sharding = None
    for fn in programs:
        sharding = jax.block_until_ready(fn(x[0])).sharding
    lad = Ladder(jax, graphs, programs, sharding, width, ctx.chips, K,
                 cfg["nonfinite_sources"], make,
                 (cfg["body"]["a"], cfg["body"]["b"]), dtype)
    inits = _place(jax, x, sharding, R, K)
    # warm-up with placed inputs, as the window calls them; its time per
    # graph sets how many graphs the window keeps in flight
    for fn, rung_inits in zip(programs, inits):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(rung_inits[0]))
        lad.graph_s.append(time.perf_counter() - t0)
    ctx.mark("compile_warm")
    return lad, jax.block_until_ready(inits)


@dataclasses.dataclass
class RungRun:
    grain: int
    graphs: int
    seconds: float
    outputs: List[tuple]        # (graph index, output) kept for the check
    done: List[float] = dataclasses.field(default_factory=list)
    trace: object = None

    def intervals_ms(self) -> str:
        """min/median/max of the time between graph completions."""
        d = np.diff(self.done) * 1e3
        if not len(d):
            return "-"
        return f"{d.min():.4f}/{np.median(d):.4f}/{d.max():.4f}"


def run_rung(lad: Ladder, r: int, inits, seconds: float, *, queue_s: float,
             keep=None, max_graphs: int = 0) -> RungRun:
    """Whole graphs back to back for ``seconds`` (or ``max_graphs``).

    About ``queue_s`` of work stays in flight, so a stall of the host
    shorter than that leaves the device busy. The outputs of graphs whose
    index ``keep`` accepts, and of the last graph, are kept for the check
    (all of them where ``keep`` is None)."""
    fn = lad.programs[r]
    graph_s = lad.graph_s[r]
    depth = max(2, min(64, int(np.ceil(queue_s / graph_s))))
    kept, pending, done = [], collections.deque(), []
    n = 0
    t0 = time.perf_counter()
    while True:
        out = fn(inits[n % len(inits)])
        if keep is None or keep(n):
            kept.append((n, out))
        n += 1
        pending.append(out)
        if len(pending) > depth:
            pending.popleft().block_until_ready()
            done.append(time.perf_counter())
        if max_graphs and n >= max_graphs:
            break
        if (not max_graphs and time.perf_counter() - t0
                + len(pending) * graph_s >= seconds):
            break
    for p in pending:
        p.block_until_ready()
        done.append(time.perf_counter())
    if not kept or kept[-1][0] != n - 1:
        kept.append((n - 1, out))
    return RungRun(lad.graphs[r].kernel.iterations, n, done[-1] - t0, kept,
                   done)


def window(ctx, lad: Ladder, inits, seconds: float, seed: int) -> List[RungRun]:
    """The timed window: the rungs in ascending grain, equal shares."""
    every = ctx.traffic["compare_every"]
    offsets = np.random.default_rng(
        np.random.SeedSequence([seed, 2])).integers(0, every, len(lad.graphs))
    share = seconds / len(lad.graphs)
    return [run_rung(lad, r, inits[r], share,
                     queue_s=ctx.traffic["queue_seconds"],
                     keep=lambda i, o=int(offsets[r]): i % every == o)
            for r in range(len(lad.graphs))]


def traced_window(ctx, lad: Ladder, inits) -> List[RungRun]:
    """A few graphs per rung, each rung in a profiler session of its own;
    every output is checked."""
    n = ctx.traffic["trace_graphs_per_rung"]
    runs = []
    for r in range(len(lad.graphs)):
        with ctx.profile(f"rung{r}") as box:
            run = run_rung(lad, r, inits[r], 0.0,
                           queue_s=ctx.traffic["queue_seconds"], max_graphs=n)
        run.trace = box.trace
        runs.append(run)
    return runs


def references(lad: Ladder, inits, dtype) -> List[object]:
    """The reference's final states, per rung a (K, W, P) array."""
    jnp = lad.jax.numpy
    pattern = reference.load_pattern(lad.graphs[0].pattern)
    refs = []
    for g, rung_inits in zip(lad.graphs, inits):
        x = jnp.stack([lad.jax.device_put(i, lad.jax.devices()[0])
                       for i in rung_inits])
        refs.append(reference.run_graphs(
            x, combine=pattern.combine, steps=g.steps,
            kind=g.kernel.kind, iterations=g.kernel.iterations,
            a=lad.body[0], b=lad.body[1], dtype=dtype))
    return refs


def check(lad: Ladder, runs: List[RungRun], refs) -> Dict[str, object]:
    """Compare each kept output of the window with its reference."""
    jax = lad.jax
    per_graph = []
    for run, ref in zip(runs, refs):
        placed = [jax.device_put(ref[k], lad.sharding)
                  for k in range(ref.shape[0])]
        for i, out in run.outputs:
            per_graph.append(reference.compare(out, placed[i % len(placed)]))
    got = jax.device_get(per_graph)
    mism = np.array([int(m) for m, _, _ in got])
    rel = np.array([float(e) for _, e, _ in got])
    finite = np.array([int(f) for _, _, f in got])
    return dict(compared=len(got), class_mismatch=int(mism.sum()),
                finite_rel_err=float(rel.max()),
                finite_min=int(finite.min()),
                every_rung=all(run.outputs for run in runs),
                graph_mismatch=mism, graph_rel_err=rel)


def control_check(lad: Ladder, inits, refs) -> Dict[str, object]:
    """The control: the reference in the nearest precision below the
    configuration's in the program's place, compared with the reference
    exactly as the program's outputs are."""
    low = references(lad, inits, reference.lower(lad.dtype))
    runs = [RungRun(g.kernel.iterations, low_r.shape[0], 0.0,
                    [(k, lad.jax.device_put(low_r[k], lad.sharding))
                     for k in range(low_r.shape[0])])
            for g, low_r in zip(lad.graphs, low)]
    return check(lad, runs, refs)


def memory_peak_bytes(devices) -> int:
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices if d.memory_stats()]
    return int(max(peaks)) if peaks else 0


def rungs_for_metg(lad: Ladder, runs: List[RungRun]) -> List[Rung]:
    out = []
    for g, run in zip(lad.graphs, runs):
        out.append(Rung(grain=run.grain, graphs=run.graphs,
                        seconds=run.seconds,
                        flops_per_graph=float(g.num_tasks * task_flops(
                            g.kernel.kind, g.payload, g.kernel.iterations)),
                        tasks_per_graph=g.num_tasks, chips=lad.chips))
    return out


def run(ctx) -> Dict[str, object]:
    """One run of the cell: set-up, the window (or the traced rungs), the
    check. Returns what the harness prints."""
    jax = ctx.jax
    lad, inits = setup(ctx)
    ctx.setup_done()
    if ctx.trace:
        runs = traced_window(ctx, lad, inits)
    else:
        runs = window(ctx, lad, inits, ctx.seconds, ctx.seed)
    ctx.window_done()
    mem = memory_peak_bytes(ctx.devices)
    lad.programs = []  # the program's state goes before the reference runs
    refs = references(lad, inits, lad.dtype)
    res = check(lad, runs, refs)
    limits = ctx.limits
    checks = {
        "class_mismatch": (res["class_mismatch"], limits["class_mismatch"]),
        "finite_rel_err": (res["finite_rel_err"], limits["finite_rel_err"]),
    }
    ctx.say(f"check: {res['compared']} outputs compared of "
            f"{sum(r.graphs for r in runs)} graphs run, every rung: "
            f"{res['every_rung']}, fewest finite values in one: "
            f"{res['finite_min']}")
    bad = ((res["graph_mismatch"] > limits["class_mismatch"])
           | (res["graph_rel_err"] > limits["finite_rel_err"]))
    rungs = rungs_for_metg(lad, runs)
    end_to_end = {}
    if not ctx.trace:
        metg = metg_seconds(rungs)
        end_to_end["metg_ns"] = None if metg is None else metg * 1e9
        end_to_end["gflops_coarse"] = rungs[-1].flops_per_second / 1e9
        for r, run_ in zip(rungs, runs):
            ctx.say(f"rung grain={r.grain} graphs={r.graphs} "
                    f"seconds={r.seconds!r} gflops="
                    f"{r.flops_per_second / 1e9!r} granularity_ns="
                    f"{r.granularity_s * 1e9!r} graph_ms(min/med/max)="
                    f"{run_.intervals_ms()}")
    record = dict(
        chips=lad.chips, width=lad.width, steps=lad.graphs[0].steps,
        payload=lad.graphs[0].payload, kind=lad.graphs[0].kernel.kind,
        rungs=[dict(grain=r.grain, graphs=r.graphs, seconds=r.seconds,
                    trace=r.trace) for r in runs])
    return dict(end_to_end=end_to_end, record=record, checks=checks,
                correct=(res["class_mismatch"] <= limits["class_mismatch"]
                         and res["finite_rel_err"] <= limits["finite_rel_err"]
                         and res["every_rung"] and res["finite_min"] > 0),
                attempted=sum(r.graphs for r in runs), failed=int(bad.sum()),
                memory_peak_bytes=mem)
