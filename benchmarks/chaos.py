"""Chaos benchmark: recovery tax of the fault-tolerant ensemble runtime.

Sweeps fault classes x fault rates x device counts through
``repro.resilience.run_resilient`` and records, per configuration:

  clean_wall     production ``execute_ensemble`` wall (best of reps)
  armor_wall     resilient executor wall with NO plan armed — the cost of
                 host-stepped launches + the (disarmed) injection hook
  hook_wall      resilient wall with an armed but EMPTY plan — isolates
                 the per-launch hook itself (must be noise vs armor_wall:
                 the zero-cost contract)
  faulted_wall   resilient wall with the fault plan firing
  recovery_tax   faulted_wall / armor_wall — what the injected faults
                 cost, separated from what the armor costs
  bit_identical  recovery proof: transport/launch/straggler runs must equal
                 the clean outputs bit for bit; member-eviction runs must
                 equal the truncated-steps oracle exactly

Every row runs in a SUBPROCESS with its own forced host device count
(same protocol as benchmarks/common.py). Artifact:
``artifacts/bench/chaos.json`` with a floor_guard-style verdict block;
``floor_guard --chaos`` judges it under the two-signal rule (a tax
regression alone WARNs; only a correctness failure FAILs).

Usage:
  PYTHONPATH=src:. python -m benchmarks.chaos --smoke
  PYTHONPATH=src:. python -m benchmarks.chaos            # full sweep
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.common import ROOT, _run_subprocess_retry, bench_path

SCHEMA = 1
FAULT_CLASSES = ("transport", "launch", "member", "straggler")


@dataclasses.dataclass
class ChaosSpec:
    devices: int = 1
    pattern: str = "stencil_1d"
    width: int = 0  # 0 -> devices x overdecomposition
    overdecomposition: int = 4
    steps: int = 25
    payload: int = 64
    grain: int = 64
    members: int = 2
    steps_per_launch: int = 4
    fault: str = "transport"
    rate: float = 0.3
    seed: int = 0
    reps: int = 3
    warmup: int = 1

    def resolved_width(self) -> int:
        return self.width or self.devices * self.overdecomposition


def _plan_for(spec: ChaosSpec, num_launches: int):
    """A seeded plan for ONE fault class at the requested rate; forced to
    fire at least once (a chaos row that injected nothing proves nothing)."""
    from repro.resilience import FaultPlan, FaultSpec

    plan = FaultPlan.random(
        spec.seed, num_launches=num_launches, num_members=spec.members,
        rate=spec.rate, kinds=(spec.fault,),
        straggler_delay_s=0.02)
    if not plan.specs:
        kw = {"member": spec.members - 1} if spec.fault == "member" else \
            {"delay_s": 0.02} if spec.fault == "straggler" else {}
        plan = FaultPlan(
            (FaultSpec(spec.fault, max(0, num_launches // 2), **kw),),
            seed=spec.seed, note="forced single fault")
    return plan


def _best_wall(fn, reps: int, warmup: int) -> float:
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_chaos_inproc(spec: ChaosSpec) -> Dict:
    """One chaos measurement in the current process (the --worker body)."""
    import dataclasses as dc

    import jax
    import numpy as np

    from repro.core import GraphEnsemble, KernelSpec, TaskGraph, get_runtime
    from repro.resilience import FaultPlan, run_resilient

    devs = jax.devices()[: spec.devices]
    if len(devs) < spec.devices:
        raise RuntimeError(
            f"need {spec.devices} devices, have {len(jax.devices())}")

    def mk(steps: int, seed: int) -> TaskGraph:
        return TaskGraph(
            steps=steps, width=spec.resolved_width(), pattern=spec.pattern,
            payload=spec.payload, kernel=KernelSpec("compute_bound",
                                                    spec.grain), seed=seed)

    # heterogeneous member lengths: eviction/readmission act on real
    # ragged act schedules, not a degenerate lockstep ensemble
    members = tuple(
        mk(spec.steps - 3 * k, seed=spec.seed + k)
        for k in range(spec.members))
    ens = GraphEnsemble(members)
    rt = get_runtime("pallas_step", devices=devs,
                     steps_per_launch=spec.steps_per_launch)
    ok, why = rt.supports_ensemble(ens)
    if not ok:
        return {"skip": why, **dataclasses.asdict(spec)}

    clean = [np.asarray(o) for o in rt.execute_ensemble(ens)]
    lp = rt.build_ensemble_launches(ens)

    clean_wall = _best_wall(lambda: rt.execute_ensemble(ens),
                            spec.reps, spec.warmup)
    armor_wall = _best_wall(lambda: run_resilient(rt, ens),
                            spec.reps, spec.warmup)
    empty = FaultPlan((), seed=spec.seed, note="armed but empty")
    hook_wall = _best_wall(lambda: run_resilient(rt, ens, plan=empty),
                           spec.reps, 0)

    if spec.fault == "straggler":
        # detection row: one stall at the LAST launch (the self-calibrated
        # deadline needs clean walls first), sized off the run's own wall
        # so it provably blows factor x median regardless of the machine
        from repro.resilience import FaultSpec

        plan = FaultPlan(
            (FaultSpec("straggler", lp.num_launches - 1,
                       delay_s=max(0.05, 2.0 * armor_wall)),),
            seed=spec.seed, note="late stall sized to 2x clean wall")
    else:
        plan = _plan_for(spec, lp.num_launches)

    # the measured faulted run (fresh FaultState per rep: plans are
    # immutable, so every rep injects the identical schedule)
    res = run_resilient(rt, ens, plan=plan)
    faulted_wall = _best_wall(lambda: run_resilient(rt, ens, plan=plan),
                              max(spec.reps - 1, 1), 0)

    # ---- recovery proof --------------------------------------------------
    bit_identical = True
    if spec.fault == "member" and res.evicted:
        # evicted members: compare against the truncated-steps oracle;
        # survivors against the clean run
        oracle_members = tuple(
            dc.replace(g, steps=res.evicted[k]) if k in res.evicted else g
            for k, g in enumerate(members))
        oracle = [np.asarray(o)
                  for o in rt.execute_ensemble(GraphEnsemble(oracle_members))]
        ref = oracle
    else:
        ref = clean
    for got, want in zip(res.outputs, ref):
        if not np.array_equal(np.asarray(got), want):
            bit_identical = False

    row = dataclasses.asdict(spec)
    row.update({
        "num_launches": lp.num_launches,
        "plan": plan.describe(),
        "faults_injected": len(plan.specs),
        "clean_wall": clean_wall,
        "armor_wall": armor_wall,
        "hook_wall": hook_wall,
        "faulted_wall": faulted_wall,
        "armor_tax": armor_wall / clean_wall if clean_wall > 0 else None,
        "hook_tax": hook_wall / armor_wall if armor_wall > 0 else None,
        "recovery_tax": (faulted_wall / armor_wall
                         if armor_wall > 0 else None),
        "retries": res.retries,
        "replays": res.replays,
        "stragglers": res.stragglers,
        "evicted": {str(k): v for k, v in res.evicted.items()},
        "deadline_us": res.deadline_us,
        "deadline_source": res.deadline_source,
        "detection_latency_us": max(
            (e.overshoot_us for e in res.events
             if e.overshoot_us is not None), default=None),
        "bit_identical": bit_identical,
    })
    return row


def run_chaos_worker(spec: ChaosSpec, timeout: int = 1800) -> Dict:
    """Run one chaos row in a subprocess with a forced device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    env.setdefault("REPRO_COST_MODEL", "off")
    out, attempts = _run_subprocess_retry(
        [sys.executable, "-m", "benchmarks.chaos", "--worker"],
        what=f"chaos worker ({spec.fault}@{spec.devices}d)",
        env=env, timeout=timeout,
        input_text=json.dumps(dataclasses.asdict(spec)))
    row = json.loads(out.stdout.strip().splitlines()[-1])
    if attempts:
        row["worker_retries"] = attempts
    return row


def _verdict(rows: List[Dict]) -> Dict:
    """The floor_guard-facing summary: worst tax per fault class + the
    single correctness bit the two-signal rule hinges on."""
    judged = [r for r in rows if "skip" not in r]
    per_class: Dict[str, Dict] = {}
    for cls in FAULT_CLASSES:
        cls_rows = [r for r in judged if r["fault"] == cls]
        if not cls_rows:
            continue
        per_class[cls] = {
            "rows": len(cls_rows),
            "max_recovery_tax": max(r["recovery_tax"] for r in cls_rows),
            "bit_identical": all(r["bit_identical"] for r in cls_rows),
            "total_retries": sum(r["retries"] for r in cls_rows),
            "total_replays": sum(r["replays"] for r in cls_rows),
        }
    return {
        "recovery_bit_identical": all(r["bit_identical"] for r in judged),
        "max_armor_tax": max((r["armor_tax"] for r in judged), default=None),
        "max_hook_tax": max((r["hook_tax"] for r in judged), default=None),
        "per_class": per_class,
        "devices_proven": sorted(
            {r["devices"] for r in judged if r["bit_identical"]}),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true",
                    help="read one ChaosSpec JSON on stdin, print row JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, one rate, devices 1+4")
    ap.add_argument("--devices", type=int, nargs="*", default=None)
    ap.add_argument("--rates", type=float, nargs="*", default=None)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)

    if a.worker:
        spec = ChaosSpec(**json.loads(sys.stdin.read()))
        print(json.dumps(run_chaos_inproc(spec)))
        return 0

    devices = a.devices if a.devices else [1, 4]
    rates = a.rates if a.rates else ([0.3] if a.smoke else [0.1, 0.3, 0.6])
    steps, reps = (13, 2) if a.smoke else (25, 3)
    rows: List[Dict] = []
    for dev in devices:
        for cls in FAULT_CLASSES:
            for rate in rates:
                # straggler rows need enough launches for the detector's
                # warmup window (3 clean walls) before the injected stall
                row_steps = max(steps, 21) if cls == "straggler" else steps
                spec = ChaosSpec(devices=dev, fault=cls, rate=rate,
                                 steps=row_steps, reps=reps,
                                 seed=FAULT_CLASSES.index(cls) * 100 + dev)
                t0 = time.perf_counter()
                row = run_chaos_worker(spec)
                rows.append(row)
                tag = (f"{cls}@{dev}d rate={rate}")
                if "skip" in row:
                    print(f"chaos: {tag}: SKIP ({row['skip']})")
                    continue
                print(f"chaos: {tag}: recovery_tax="
                      f"{row['recovery_tax']:.2f}x "
                      f"(retries={row['retries']} replays={row['replays']} "
                      f"stragglers={row['stragglers']}) "
                      f"bit_identical={row['bit_identical']} "
                      f"[{time.perf_counter() - t0:.0f}s]")
    art = {
        "schema": SCHEMA,
        "smoke": bool(a.smoke),
        "rows": rows,
        "verdict": _verdict(rows),
    }
    out = a.out or bench_path("chaos.json")
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    v = art["verdict"]
    print(f"chaos: bit-identical recovery on devices "
          f"{v['devices_proven']}: {v['recovery_bit_identical']} "
          f"(armor tax <= {v['max_armor_tax']:.2f}x, hook tax <= "
          f"{v['max_hook_tax']:.2f}x) -> {out}")
    return 0 if v["recovery_bit_identical"] else 1


if __name__ == "__main__":
    sys.exit(main())
