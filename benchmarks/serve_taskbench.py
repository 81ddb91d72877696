"""Serving benchmark: tail latency of the continuous-batching fabric.

The serving analogue of Task Bench's METG axis: requests (seeded task
graphs with arrival times, priorities, and priced deadlines) stream into
``repro.serving.ServingFabric``, which packs compatible requests into
stacked cohorts and churns membership mid-run (retire -> re-admit into
freed (K, S) act-mask slots, no recompile). Per configuration the row
records:

  p50/p95/p99 latency   request completion minus arrival, milliseconds
  throughput_rps        completed requests per second of serving wall
  slot_utilization      active-slot-launches / (K x launches)
  cohort census         stacked vs per-step cohorts, membership changes,
                        recompiles (must be 0), stacking-verdict reasons
  bit_identical         every request's output vs its serial same-K
                        oracle (the fabric's correctness contract)

Every row runs in a SUBPROCESS with its own forced host device count
(same protocol as benchmarks/chaos.py). Artifact:
``artifacts/bench/serve_taskbench.json`` with a floor_guard-style verdict
block; ``floor_guard --serve`` judges it under the two-signal rule (a p99
regression alone WARNs; lost bit-identity or cratered utilization FAILs).

Usage:
  PYTHONPATH=src:. python -m benchmarks.serve_taskbench --smoke
  PYTHONPATH=src:. python -m benchmarks.serve_taskbench   # full sweep
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List

from benchmarks.common import ROOT, _run_subprocess_retry, bench_path

SCHEMA = 1


@dataclasses.dataclass
class ServeSpec:
    devices: int = 1
    slots: int = 4  # K act-mask slots per cohort
    width: int = 32
    payload: int = 32
    grain: int = 4
    steps_per_launch: int = 4
    requests: int = 18
    arrival_scale_s: float = 0.002  # mean Poisson interarrival gap
    deadline_factor: float = 8.0
    seed: int = 0
    verify: bool = True


def _request_stream(spec: ServeSpec) -> List:
    """A mixed-(pattern, T, W) stream with guaranteed churn structure.

    The head is deterministic: ``slots`` founders plus enough follow-on
    compatible requests that the first stacked cohort MUST retire members
    and re-admit from the queue (the >= 2 membership-changes acceptance
    criterion is structural, not luck). The tail is a seeded-Poisson mix
    over three more compatibility classes — wider stencils (different
    block shape -> second stacked cohort), radius-2 nearest (different
    tables -> third), and all_to_all (allgather plan -> per-step cohort)
    — so the packer demonstrably routes the stream into separate cohorts
    instead of one degraded tuple ensemble."""
    import numpy as np

    from repro.serving import make_request

    rng = np.random.default_rng(spec.seed)
    gaps = rng.exponential(spec.arrival_scale_s, size=max(spec.requests, 1))
    arrivals = np.cumsum(gaps)
    k = spec.slots
    reqs = []

    def add(i: int, **kw):
        reqs.append(make_request(
            i, width=kw.pop("width", spec.width), payload=spec.payload,
            arrival_s=float(arrivals[i]) if i else 0.0,
            seed=spec.seed + 101 * i,
            priority=int(rng.integers(0, 3)), **kw))

    head = min(spec.requests, 2 * k + 2)
    for i in range(head):
        # founders get long-ish staggered horizons; the follow-ons are
        # short so retirements free slots while the queue is non-empty
        steps = 5 + 4 * (i % k) if i < k else 5 + 2 * (i % 3)
        add(i, steps=steps, pattern="stencil_1d")
    tail_mix = (
        dict(pattern="stencil_1d", width=2 * spec.width),
        dict(pattern="nearest", radius=2),
        dict(pattern="all_to_all"),
        dict(pattern="stencil_1d"),
    )
    for i in range(head, spec.requests):
        add(i, steps=int(rng.integers(5, 14)),
            **tail_mix[(i - head) % len(tail_mix)])
    return reqs


def run_serve_inproc(spec: ServeSpec) -> Dict:
    """One serving measurement in the current process (--worker body)."""
    import jax

    from repro.core import get_runtime
    from repro.serving import ServingFabric

    devs = jax.devices()[: spec.devices]
    if len(devs) < spec.devices:
        raise RuntimeError(
            f"need {spec.devices} devices, have {len(jax.devices())}")
    rt = get_runtime("pallas_step", devices=devs,
                     steps_per_launch=spec.steps_per_launch)
    fabric = ServingFabric(rt, max_slots=spec.slots,
                           deadline_factor=spec.deadline_factor,
                           verify=spec.verify)
    reqs = _request_stream(spec)
    rep = fabric.serve(reqs)

    stacked = [c for c in rep.cohorts if c.kind == "stacked"]
    stepwise = [c for c in rep.cohorts if c.kind != "stacked"]
    util_num = sum(c.slot_utilization * c.slots * c.launches_run
                   for c in rep.cohorts)
    util_den = sum(c.slots * c.launches_run for c in rep.cohorts)
    pct = rep.latency_percentiles_s()
    row = dataclasses.asdict(spec)
    row.update({
        "completed": len(rep.completed),
        "deadline_evicted": sum(
            1 for o in rep.outcomes if o.status == "deadline_evicted"),
        "p50_ms": pct["p50"] * 1e3,
        "p95_ms": pct["p95"] * 1e3,
        "p99_ms": pct["p99"] * 1e3,
        "throughput_rps": (len(rep.completed) / rep.wall_s
                           if rep.wall_s > 0 else None),
        "serve_wall_s": rep.wall_s,
        "slot_utilization": util_num / util_den if util_den else 1.0,
        "stacked_cohorts": len(stacked),
        "stepwise_cohorts": len(stepwise),
        "max_stacked_membership_changes": max(
            (c.membership_changes for c in stacked), default=0),
        "mid_run_admissions": sum(c.admitted_mid_run for c in rep.cohorts),
        "recompiles": sum(c.recompiles or 0 for c in rep.cohorts),
        "bit_identical": rep.bit_identical,
        "cohorts": [dataclasses.asdict(c) for c in rep.cohorts],
    })
    return row


def run_serve_worker(spec: ServeSpec, timeout: int = 1800) -> Dict:
    """Run one serving row in a subprocess with a forced device count."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    env.setdefault("REPRO_COST_MODEL", "off")
    out, attempts = _run_subprocess_retry(
        [sys.executable, "-m", "benchmarks.serve_taskbench", "--worker"],
        what=f"serve worker (K={spec.slots}@{spec.devices}d)",
        env=env, timeout=timeout,
        input_text=json.dumps(dataclasses.asdict(spec)))
    row = json.loads(out.stdout.strip().splitlines()[-1])
    if attempts:
        row["worker_retries"] = attempts
    return row


def _verdict(rows: List[Dict]) -> Dict:
    """The floor_guard-facing summary. ``dynamic_cohort`` is the
    continuous-batching acceptance bit: some stacked cohort churned
    membership >= 2 times with zero recompiles."""
    judged = [r for r in rows if "skip" not in r]
    return {
        "bit_identical": all(r["bit_identical"] for r in judged),
        "dynamic_cohort": any(
            r["max_stacked_membership_changes"] >= 2
            and r["recompiles"] == 0 for r in judged),
        "min_stacked_cohorts": min(
            (r["stacked_cohorts"] for r in judged), default=0),
        "min_slot_utilization": min(
            (r["slot_utilization"] for r in judged), default=None),
        "total_deadline_evictions": sum(
            r["deadline_evicted"] for r in judged),
        "p99_ms_by_slots": {
            str(r["slots"]): r["p99_ms"] for r in judged},
        "throughput_by_slots": {
            str(r["slots"]): r["throughput_rps"] for r in judged},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true",
                    help="read one ServeSpec JSON on stdin, print row JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, K in {2, 4}, 2 devices")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--slots", type=int, nargs="*", default=None)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)

    if a.worker:
        spec = ServeSpec(**json.loads(sys.stdin.read()))
        print(json.dumps(run_serve_inproc(spec)))
        return 0

    devices = a.devices if a.devices else (2 if a.smoke else 4)
    slot_sweep = a.slots if a.slots else ([2, 4] if a.smoke else [2, 4, 8])
    requests = a.requests if a.requests else (14 if a.smoke else 32)
    rows: List[Dict] = []
    for k in slot_sweep:
        spec = ServeSpec(devices=devices, slots=k, requests=requests,
                         seed=k)
        t0 = time.perf_counter()
        row = run_serve_worker(spec)
        rows.append(row)
        print(f"serve: K={k}@{devices}d: p50={row['p50_ms']:.1f}ms "
              f"p99={row['p99_ms']:.1f}ms "
              f"thpt={row['throughput_rps']:.1f}req/s "
              f"util={row['slot_utilization']:.2f} "
              f"(stacked={row['stacked_cohorts']} "
              f"churn={row['max_stacked_membership_changes']} "
              f"recompiles={row['recompiles']}) "
              f"bit_identical={row['bit_identical']} "
              f"[{time.perf_counter() - t0:.0f}s]")
    art = {
        "schema": SCHEMA,
        "smoke": bool(a.smoke),
        "rows": rows,
        "verdict": _verdict(rows),
    }
    out = a.out or bench_path("serve_taskbench.json")
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    v = art["verdict"]
    print(f"serve: bit_identical={v['bit_identical']} "
          f"dynamic_cohort={v['dynamic_cohort']} "
          f"stacked_cohorts>={v['min_stacked_cohorts']} -> {out}")
    ok = (v["bit_identical"] and v["dynamic_cohort"]
          and v["min_stacked_cohorts"] >= 2)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
