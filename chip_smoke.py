#!/usr/bin/env python3
"""Bring-up check: the Task Bench main path runs on a TPU and agrees with XLA.

    python chip_smoke.py             # one chip: every pallas_step plan + serving
    python chip_smoke.py --chips 4   # four chips: the multi-device path only

Drives ``pallas_step`` through the entry points a user calls —
``get_runtime("pallas_step", ...)`` with its plan dispatch, and
``ServingFabric.serve`` — at the paper's protocol sizes (configs/taskbench.py
PAPER: 1000 steps, payload 64, the compute_bound kernel; grain 64) and checks
every result against the ``fused`` backend (plain XLA, no Pallas) on one chip
of the same machine, under the explicit tolerance of ``_compare``.

A compute_bound body contracts every state to its fixed point 0.2 long
before step 1000, so a protocol-size result would match any oracle whatever
the combine did. Each phase therefore also runs an information-preserving
twin of its graph — the same plan with the ``empty`` body over a few steps —
whose output is the combine's alone, and compares that too.

Each phase prints one line (plan, steps per launch, compile and run seconds,
max |error| against ``fused``); the last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``. Exits non-zero without that line when JAX
finds no TPU, and with ``"ok": false`` when any phase fails: a declined graph,
an exception, a wrong plan or a mismatch.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402

RTOL = 1e-5
ATOL = 1e-6
STEPS = 1000
PAYLOAD = 64
GRAIN = 64
#: steps of the information-preserving twins: 11 combine steps, so a
#: steps_per_launch=8 plan runs one full launch and one masked tail
TWIN_STEPS = 12

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), from JAX's own monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        self.total_seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.total_seconds += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


class PhaseFailed(AssertionError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def _rtol(graph) -> float:
    """RTOL, plus one f32 ulp (2**-23) of drift per step where the body does
    not contract it: pallas_step weighs n dependencies by 1/n rounded once,
    fused divides their sum by n, and an averaging-only (empty) or
    mean-preserving (memory_bound) body carries the difference forward,
    while the compute_bound FMA halves it every iteration."""
    k = graph.kernel
    contracts = k.kind == "compute_bound" and k.iterations > 0
    return RTOL + (1 if contracts else graph.steps) * 2.0 ** -23


def _compare(name: str, got, want, graph) -> float:
    got = np.asarray(got)
    want = np.asarray(want)
    _check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    _check(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = float(np.max(np.abs(got - want)))
    rtol = _rtol(graph)
    _check(bool(np.allclose(got, want, rtol=rtol, atol=ATOL)),
           f"{name}: max |error| {err:.3e} outside rtol={rtol:.3g} "
           f"atol={ATOL}")
    return err


def _timed(jax, fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _span(index: slice, size: int) -> str:
    """'start:stop' of one shard's slice along an axis of ``size``."""
    return f"{index.start or 0}:{size if index.stop is None else index.stop}"


def _resolve(rt, graph, plan: str, steps_per_launch: int):
    ok, why = rt.supports(graph)
    _check(ok, f"pallas_step declined {graph.describe()}: {why}")
    got = rt._schedule_for_graph(graph)
    _check((got.kind, got.steps_per_launch) == (plan, steps_per_launch),
           f"{graph.describe()} resolved plan {got.kind} S="
           f"{got.steps_per_launch}, expected {plan} S={steps_per_launch}")
    return got


def graph_phase(ctx, name: str, rt, graph, *, plan: str, steps_per_launch: int,
                twin_steps: int = TWIN_STEPS, expect_devices: int = 1):
    """Build, run twice (compile + steady), compare with fused on one chip;
    then the same for the graph's information-preserving twin."""
    jax, jnp = ctx["jax"], ctx["jnp"]
    from repro.core.task_kernels import KernelSpec, initial_state

    _resolve(rt, graph, plan, steps_per_launch)
    init = initial_state(graph.width, graph.payload, graph.seed)
    fn = rt.build(graph)
    ctx["meter"].take()
    out, _ = _timed(jax, fn, jnp.array(init, copy=True))
    compile_s = ctx["meter"].take()
    out, run_s = _timed(jax, fn, jnp.array(init, copy=True))
    devices = sorted({s.device.id for s in out.addressable_shards})
    _check(len(devices) == expect_devices,
           f"{name}: state on devices {devices}, expected {expect_devices}")
    err = _compare(name, out, ctx["fused"].execute(graph), graph)

    twin = dataclasses.replace(graph, steps=twin_steps,
                               kernel=KernelSpec("empty", 0))
    _resolve(rt, twin, plan, min(steps_per_launch, twin_steps - 1))
    info_err = _compare(f"{name} twin", rt.execute(twin),
                        ctx["fused"].execute(twin), twin)
    return dict(phase=name, plan=plan, S=steps_per_launch,
                launches=rt.dispatches_per_run(graph),
                compile_s=compile_s, run_s=run_s, max_abs_err=err,
                twin_max_abs_err=info_err,
                shards=[f"rows {_span(s.index[0], graph.width)}@{s.device.id}"
                        for s in out.addressable_shards])


def serve_phase(ctx):
    """Stacked and stepwise cohorts through ServingFabric.serve, every
    request verified against its serial oracle (bit-identity) and fused."""
    from repro.core.runtimes.base import get_runtime
    from repro.core.task_kernels import KernelSpec
    from repro.serving import ServingFabric, make_request

    rt = get_runtime("pallas_step", devices=ctx["devices"], steps_per_launch=8,
                     cost_model=ctx["cost_model"])
    # empty body: served outputs stay informative (see module docstring),
    # so a wrong freeze step or slot mix-up shows up against both oracles
    kernel = KernelSpec("empty", 0)
    mix = [("stencil_1d", 4096, (1000, 700, 450, 200)),
           ("stencil_1d", 1024, (300, 1000, 600, 800)),
           ("fft", 2048, (500, 250, 1000, 350))]
    requests = [
        make_request(len(mix[0][2]) * i + j, steps=t, width=w, pattern=p,
                     payload=PAYLOAD, kernel=kernel, seed=10 * i + j)
        for i, (p, w, steps) in enumerate(mix) for j, t in enumerate(steps)]
    fabric = ServingFabric(rt, max_slots=4, verify=True)
    ctx["meter"].take()
    t0 = time.perf_counter()
    report = fabric.serve(requests)
    total_s = time.perf_counter() - t0
    compile_s = ctx["meter"].take()

    kinds = [c.kind for c in report.cohorts]
    stacked = [c for c in report.cohorts if c.kind == "stacked"]
    _check(len(stacked) >= 2 and all(c.slots == 4 for c in stacked),
           f"serve: expected >= 2 stacked K=4 cohorts, got "
           f"{[(c.kind, c.slots) for c in report.cohorts]}")
    _check(len(report.completed) == len(requests),
           f"serve: {len(report.completed)}/{len(requests)} completed")
    unverified = [o.rid for o in report.outcomes if o.bit_identical is None]
    _check(not unverified, f"serve: requests {unverified} not verified")
    mism = [o.rid for o in report.outcomes if not o.bit_identical]
    _check(not mism, f"serve: requests {mism} differ from their oracle")
    err = 0.0
    for o in report.outcomes:
        g = dataclasses.replace(o.graph, steps=o.effective_steps)
        err = max(err, _compare(f"serve rid {o.rid}", o.output,
                                ctx["fused"].execute(g), g))
    return dict(phase="serve", plan="+".join(kinds),
                S=[c.steps_per_launch for c in report.cohorts],
                launches=[c.launches_run for c in report.cohorts],
                compile_s=compile_s, run_s=report.wall_s,
                verify_s=total_s - report.wall_s, max_abs_err=err,
                requests=len(requests), bit_identical=report.bit_identical)


def one_chip_phases(ctx):
    from repro.core.graph import TaskGraph
    from repro.core.runtimes.base import get_runtime
    from repro.core.task_kernels import KernelSpec

    devs, cm = ctx["devices"], ctx["cost_model"]
    grain = KernelSpec("compute_bound", GRAIN)

    def rt(**opts):
        return get_runtime("pallas_step", devices=devs, cost_model=cm, **opts)

    def g(pattern, width, kernel=grain):
        return TaskGraph(steps=STEPS, width=width, pattern=pattern,
                         kernel=kernel, payload=PAYLOAD)

    stencil = g("stencil_1d", 4096)
    yield lambda: graph_phase(ctx, "stencil_halo", rt(steps_per_launch=1),
                              stencil, plan="halo", steps_per_launch=1)
    yield lambda: graph_phase(ctx, "stencil_blocked", rt(steps_per_launch=8),
                              stencil, plan="halo", steps_per_launch=8)
    yield lambda: graph_phase(ctx, "fft_stride", rt(steps_per_launch=1),
                              g("fft", 4096), plan="stride",
                              steps_per_launch=1)
    # the widest all-gather launch that fits VMEM (tests/test_tpu_compile.py)
    yield lambda: graph_phase(ctx, "spread_allgather",
                              rt(steps_per_launch=4),
                              g("spread", 512), plan="allgather",
                              steps_per_launch=4)
    # a (4096, 2048) f32 scratch sweep outgrows VMEM, so rows are tiled
    yield lambda: graph_phase(ctx, "memory_bound",
                              rt(steps_per_launch=1, block_rows=256),
                              g("stencil_1d", 4096,
                                KernelSpec("memory_bound", 16)),
                              plan="halo", steps_per_launch=1)
    yield lambda: serve_phase(ctx)


def four_chip_phases(ctx):
    from repro.core.graph import GraphEnsemble, TaskGraph
    from repro.core.runtimes.base import get_runtime
    from repro.core.task_kernels import KernelSpec

    jax, jnp = ctx["jax"], ctx["jnp"]
    devs, cm = ctx["devices"], ctx["cost_model"]
    grain = KernelSpec("compute_bound", GRAIN)
    W = 16384

    def g(pattern, steps=STEPS, width=W):
        return TaskGraph(steps=steps, width=width, pattern=pattern,
                         kernel=grain, payload=PAYLOAD)

    piped = get_runtime("pallas_step", devices=devs, steps_per_launch=8,
                        cost_model=cm)

    def stencil():
        row = graph_phase(ctx, "stencil_pipelined_4chip", piped,
                          g("stencil_1d"), plan="halo", steps_per_launch=8,
                          expect_devices=4)
        L = 1 + -(-(STEPS - 1) // 8)
        _check(row["launches"] == 1 + 2 * (L - 1),
               f"stencil: {row['launches']} launches, pipelined split "
               f"expects {1 + 2 * (L - 1)}")
        return row

    yield stencil
    # twin of 14 steps: 13 butterfly stages, the last off-block (stride 4096
    # = one device's rows), so a wrong partner block changes the result
    yield lambda: graph_phase(
        ctx, "fft_stride_4chip",
        get_runtime("pallas_step", devices=devs, steps_per_launch=1,
                    cost_model=cm),
        g("fft"), plan="stride", steps_per_launch=1, twin_steps=14,
        expect_devices=4)

    def ensemble():
        from repro.core.task_kernels import initial_state

        rt = get_runtime("pallas_step", devices=devs, steps_per_launch=8,
                         member_shards=2, cost_model=cm)
        # W/2 = 4096 rows per device: a blocked launch on 8192 rows
        # outgrows VMEM (tests/test_tpu_compile.py)
        members = tuple(
            dataclasses.replace(g("stencil_1d", steps=t, width=W // 2),
                                seed=k)
            for k, t in enumerate((1000, 900, 800, 700)))
        ens = GraphEnsemble(members)
        ok, why = rt.stacking_verdict(ens)
        _check(ok, f"ensemble not stacked: {why}")
        inits = [initial_state(m.width, m.payload, m.seed) for m in members]
        fn = rt.build_ensemble(ens)
        ctx["meter"].take()
        outs, _ = _timed(jax, fn, tuple(jnp.array(x, copy=True)
                                        for x in inits))
        compile_s = ctx["meter"].take()
        outs, run_s = _timed(jax, fn, tuple(jnp.array(x, copy=True)
                                            for x in inits))
        # the stacked (K, W, P) state as the runtime places it: one
        # (members, rows) block per device of the (row, member) mesh
        state = rt.build_ensemble_launches(ens).init_fn(inits)
        shards = sorted(
            (f"members {_span(s.index[0], len(members))} rows "
             f"{_span(s.index[1], W // 2)}", s.device.id)
            for s in state.addressable_shards)
        _check(len({d for _, d in shards}) == 4
               and len({b for b, _ in shards}) == 4,
               f"stacked state blocks {shards}: expected 4 distinct "
               f"(members, rows) blocks on 4 devices")
        err = max(_compare(f"ensemble member {k}", o,
                           ctx["fused"].execute(m), m)
                  for k, (o, m) in enumerate(zip(outs, members)))
        return dict(phase="ensemble_member_sharded_4chip", plan="halo",
                    S=rt._ensemble_steps_per_launch(ens), K=len(members),
                    member_shards=2,
                    launches=rt.ensemble_dispatches_per_run(ens),
                    compile_s=compile_s, run_s=run_s, max_abs_err=err,
                    state_shards=[f"{b}@{d}" for b, d in shards])

    yield ensemble


def _line(row) -> str:
    keys = [k for k in row if k != "phase"]
    parts = []
    for k in keys:
        v = row[k]
        parts.append(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}")
    return f"phase {row['phase']}: " + " ".join(parts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device path, on four chips")
    args = ap.parse_args(argv)

    cache = compile_cache.enable()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: JAX found platform {dev0.platform!r} "
              f"({dev0.device_kind}), not a TPU; nothing was run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.core.runtimes.base import get_runtime
    from repro.kernels import ops
    from repro.kernels.probes import analytic_cost_model

    if ops._interpret():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 2
    ctx = dict(jax=jax, jnp=jnp, devices=devices[:args.chips],
               meter=CompileMeter(jax),
               # pinned, so no cached calibration file steers a schedule
               cost_model=analytic_cost_model(),
               fused=get_runtime("fused", devices=devices[:1]))
    print(f"chip_smoke: {len(ctx['devices'])} x {dev0.device_kind}, "
          f"compile cache {cache}", flush=True)

    phases = four_chip_phases(ctx) if args.chips == 4 else one_chip_phases(ctx)
    rows, failed = [], []
    for phase in phases:
        try:
            row = phase()
        except Exception as e:  # every failure fails the run; keep going
            traceback.print_exc()
            failed.append(f"{type(e).__name__}: {e}")
            print(f"phase FAILED: {failed[-1]}", flush=True)
            continue
        rows.append(row)
        print(_line(row), flush=True)

    result = {
        "ok": not failed,
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(ctx["devices"])},
        "compile_s_total": ctx["meter"].total_seconds,
        "persistent_cache_hits": ctx["meter"].cache_hits,
        "failed": failed,
    }
    print(json.dumps(result))
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
