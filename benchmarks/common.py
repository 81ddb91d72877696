"""Shared measurement machinery for the Task Bench benchmarks.

All benchmarks follow the paper's protocol (§6): a task graph of `steps`
timesteps x `width` points, the compute-bound kernel with the grain knob
`iterations`, reps with warmup, best-of-reps walls; METG extracted at the
50% efficiency threshold.

Device-count sweeps run in SUBPROCESSES (`run_worker`) so each point gets
its own forced host-device count — the main process never touches
XLA_FLAGS. On this container every host device multiplexes ONE physical
core, so absolute FLOP/s do not scale with devices; efficiency is
peak-normalized per configuration, which keeps the paper's runtime-overhead
reading valid (documented in EXPERIMENTS.md §Reproduction).
"""
from __future__ import annotations

import argparse
import csv
import dataclasses
import json
import os
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "artifacts", "bench")


def bench_path(name: str) -> str:
    os.makedirs(BENCH_DIR, exist_ok=True)
    return os.path.join(BENCH_DIR, name)


@dataclasses.dataclass
class SweepSpec:
    runtime: str
    pattern: str = "stencil_1d"
    devices: int = 1
    width: int = 0  # 0 -> devices x overdecomposition
    overdecomposition: int = 1
    steps: int = 50
    payload: int = 64
    grains: Tuple[int, ...] = (1, 16, 256, 4096, 16384)
    reps: int = 3
    warmup: int = 1
    #: K > 1 runs a GraphEnsemble of K independent graphs (distinct seeds,
    #: same pattern/grain) concurrently instead of one graph.
    ensemble: int = 1
    #: with ensemble > 1: also time each member alone, back-to-back, and
    #: report the summed serial wall ("serial_wall") as the no-concurrency
    #: baseline for the same process/devices/compile state.
    serial_baseline: bool = False
    #: measure these runtimes back-to-back in ONE worker process (rows carry
    #: a "runtime" key). Cross-backend wall ratios from a single process are
    #: far less noisy than ratios across separately scheduled workers.
    compare_runtimes: Tuple[str, ...] = ()
    options: Dict = dataclasses.field(default_factory=dict)
    #: label -> extra runtime options, measured back-to-back in the SAME
    #: worker process (rows carry a "variant" key): the option-sweep
    #: analogue of compare_runtimes, e.g. a steps_per_launch ladder.
    option_variants: Dict = dataclasses.field(default_factory=dict)
    #: "fused" times the backend's normal executor (whole loop in jitted
    #: programs); "per_launch" times the host-stepped EnsembleLaunchPlan
    #: (one dispatch + sync per launch — the resilience/serving cadence,
    #: where per-dispatch collective cost is not amortized into a scan).
    dispatch: str = "fused"
    #: record a span trace (repro.obs) in a SEPARATE traced execution after
    #: the timed reps — rows gain a "trace" key with the per-category wall
    #: decomposition. The timed path is untouched (DESIGN.md §10).
    trace: bool = False
    #: when tracing, also write one Chrome trace_event JSON per traced row
    #: into this directory (named <runtime>[_<variant>]_g<grain>.json)
    trace_dir: str = ""

    def resolved_width(self) -> int:
        return self.width or self.devices * self.overdecomposition


def run_sweep_inproc(spec: SweepSpec) -> List[Dict]:
    """Run inside the current process (uses existing jax device set)."""
    import jax

    from repro.core import GraphEnsemble, KernelSpec, TaskGraph, get_runtime

    devs = jax.devices()[: spec.devices]
    if len(devs) < spec.devices:
        raise RuntimeError(
            f"need {spec.devices} devices, have {len(jax.devices())}")
    rows = []
    runtimes = spec.compare_runtimes or (spec.runtime,)
    for grain in spec.grains:
        members = [
            TaskGraph(
                steps=spec.steps,
                width=spec.resolved_width(),
                pattern=spec.pattern,
                payload=spec.payload,
                kernel=KernelSpec("compute_bound", grain),
                seed=k,
            )
            for k in range(max(spec.ensemble, 1))
        ]
        variants = spec.option_variants or {"": {}}
        for name, vlabel in [(n, vl) for n in runtimes for vl in variants]:
            opts = {**spec.options, **variants[vlabel]}
            if spec.trace:
                opts["trace"] = True
            rt = get_runtime(name, devices=devs, **opts)
            serial_wall = None
            if spec.ensemble > 1:
                ens = GraphEnsemble(members)
                ok, why = rt.supports_ensemble(ens)
                if not ok:
                    rows.append({"runtime": name, "variant": vlabel,
                                 "grain": grain, "skip": why})
                    continue
                sample, stats = rt.measure_ensemble(
                    ens, reps=spec.reps, warmup=spec.warmup)
                if spec.serial_baseline:
                    # members differ only in seed (same traced program), so
                    # time ONE member and scale — avoids K redundant compiles
                    serial_wall = spec.ensemble * rt.measure(
                        members[0], reps=spec.reps,
                        warmup=spec.warmup)[0].wall_time
            elif spec.dispatch == "per_launch":
                g = members[0]
                ens = GraphEnsemble([g])
                ok, why = rt.supports_ensemble(ens)
                if not ok:
                    rows.append({"runtime": name, "variant": vlabel,
                                 "grain": grain, "skip": why})
                    continue
                sample, stats = rt.measure_launch_plan(
                    ens, reps=spec.reps, warmup=spec.warmup)
            else:
                g = members[0]
                ok, why = rt.supports(g)
                if not ok:
                    rows.append({"runtime": name, "variant": vlabel,
                                 "grain": grain, "skip": why})
                    continue
                sample, stats = rt.measure(g, reps=spec.reps,
                                           warmup=spec.warmup)
            row = {
                "runtime": name,
                "variant": vlabel,
                "grain": grain,
                "wall": sample.wall_time,
                "flops": sample.total_flops,
                "tasks": sample.num_tasks,
                "cores": sample.cores,
                "gran_us": sample.granularity_us,
                "rate": sample.flops_per_second,
                "dispatches": stats.dispatches,
            }
            if serial_wall is not None:
                row["serial_wall"] = serial_wall
            if spec.trace and spec.ensemble <= 1:
                row["trace"] = _trace_row(rt, members[0], spec,
                                          name, vlabel, grain)
            rows.append(row)
    return rows


def _trace_row(rt, graph, spec: SweepSpec, name: str, vlabel: str,
               grain: int) -> Dict:
    """One traced execution -> the row's decomposition summary (and,
    with ``trace_dir``, a Chrome trace file). Runs AFTER the timed reps so
    the warmup cost of tracing can never leak into the walls; the spans the
    timed reps left in the tracer are dropped first, so the summary covers
    this one execution."""
    import re

    from repro.obs import summarize, write_chrome_trace

    rt.tracer.clear()
    rt.trace_once(graph)
    summary = summarize(rt.tracer.spans)
    if spec.trace_dir:
        os.makedirs(spec.trace_dir, exist_ok=True)
        label = re.sub(r"[^A-Za-z0-9_.-]+", "-",
                       name + (f"_{vlabel}" if vlabel else "") + f"_g{grain}")
        write_chrome_trace(
            os.path.join(spec.trace_dir, f"{label}.json"),
            rt.tracer.spans, process_name=label)
    return summary


#: one retry for transient worker deaths (OOM kill, scheduler eviction,
#: wedged XLA compile hitting the timeout); backoff before it so a loaded
#: host gets a moment to drain
WORKER_RETRIES = 1
WORKER_RETRY_BACKOFF_S = 5.0


def _run_subprocess_retry(cmd, *, what: str, env: Dict, timeout: int,
                          input_text: Optional[str] = None,
                          retries: int = WORKER_RETRIES,
                          backoff_s: float = WORKER_RETRY_BACKOFF_S):
    """Run a benchmark subprocess with per-attempt timeout and retry.

    A sweep is hours of accumulated walls; one transiently dead worker
    must not discard all of it. Returns (CompletedProcess, attempts_used);
    raises RuntimeError naming the failure only once the retry budget is
    spent. The retry count is surfaced in the caller's JSON so an artifact
    judged after a retry says so."""
    import time as _time

    last_err = ""
    for attempt in range(retries + 1):
        if attempt:
            _time.sleep(backoff_s * attempt)
        try:
            out = subprocess.run(
                cmd, input=input_text, capture_output=True, text=True,
                timeout=timeout, env=env, cwd=ROOT)
        except subprocess.TimeoutExpired:
            last_err = f"timed out after {timeout}s"
            continue
        if out.returncode == 0:
            return out, attempt
        last_err = out.stderr[-4000:]
    raise RuntimeError(
        f"{what} failed after {retries + 1} attempts:\n{last_err}")


def run_worker(spec: SweepSpec, timeout: int = 3000) -> List[Dict]:
    """Run a sweep in a subprocess with its own forced device count.

    Each attempt gets the full ``timeout``; a transient worker death
    (timeout / nonzero exit) is retried once with backoff, and rows from a
    retried worker carry ``worker_retries`` so the artifact records it."""
    payload = json.dumps(dataclasses.asdict(spec))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={spec.devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    out, attempts = _run_subprocess_retry(
        [sys.executable, "-m", "benchmarks._worker"],
        what=f"sweep worker ({spec.runtime}, {spec.devices}d)",
        env=env, timeout=timeout, input_text=payload)
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    if attempts:
        for row in rows:
            row["worker_retries"] = attempts
    return rows


def calibrate_worker(devices: int, payload: int = 64, *, smoke: bool = False,
                     out: Optional[str] = None,
                     timeout: int = 600) -> Dict:
    """Run the cost-model probes in a subprocess and return the model dict.

    A subprocess for the same reason as ``run_worker``: the probes need
    their own forced host-device count, and the main process never touches
    XLA_FLAGS. The calibration is merged into ``out`` (default: the cache
    file every later "auto" resolution reads), and the returned snapshot
    is what the benchmarks embed in their artifact JSON — every saved
    verdict names the constants it was judged under."""
    out = out or bench_path("cost_model.json")
    cmd = [sys.executable, "-m", "repro.kernels.probes",
           "--devices", str(devices), "--payload", str(payload),
           "--out", out, "--json"]
    if smoke:
        cmd.append("--smoke")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    env.pop("XLA_FLAGS", None)  # the probes CLI sets its own forcing flag
    res, attempts = _run_subprocess_retry(
        cmd, what=f"calibration ({devices}d)", env=env, timeout=timeout)
    lines = res.stdout.strip().splitlines()
    # stdout: "cost model [...] -> path", describe() line, then the JSON
    start = next(i for i, ln in enumerate(lines) if ln.startswith("{"))
    model = json.loads("\n".join(lines[start:]))
    if attempts:
        model["worker_retries"] = attempts
    return model


def gather_impl_worker(devices: int, widths: Tuple[int, ...],
                       payload: int = 64, reps: int = 25,
                       timeout: int = 600) -> Dict[str, Dict[int, float]]:
    """Measure ``gather_global`` transport walls per (impl, width) in a
    subprocess with its own forced device count.

    This is ``probes.probe_gather_impl_us`` — one dispatched collective
    per timed call, median-of-reps (the typical per-dispatch wall; see
    the probe's docstring), the exact table
    ``schedule.choose_gather_impl`` ranks. Returns ``{impl: {width: us}}``
    for impls xla and chunked at the given device count."""
    code = (
        "import json\n"
        "from repro.kernels.probes import probe_gather_impl_us\n"
        f"t = probe_gather_impl_us({devices}, {payload},\n"
        f"    widths={tuple(widths)}, impls=('xla', 'chunked'),\n"
        f"    device_counts=({devices},), reps={reps})\n"
        "print(json.dumps(t))\n"
    )
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    out, _ = _run_subprocess_retry(
        [sys.executable, "-c", code],
        what=f"gather transport probe ({devices}d)", env=env,
        timeout=timeout)
    raw = json.loads(out.stdout.strip().splitlines()[-1])
    # json stringifies the int keys; flatten the devices level (single d)
    return {
        impl: {int(w): us for w, us in by_d.get(str(devices), {}).items()}
        for impl, by_d in raw.items()
    }


def metg_from_rows(rows: Sequence[Dict], threshold: float = 0.5,
                   peak: Optional[float] = None):
    from repro.core import GrainSample, compute_metg

    samples = [
        GrainSample(
            iterations=r["grain"], wall_time=r["wall"],
            total_flops=r["flops"], num_tasks=r["tasks"], cores=r["cores"],
        )
        for r in rows if "skip" not in r
    ]
    return compute_metg(samples, threshold=threshold, peak=peak)


def backend_options_args(ap: argparse.ArgumentParser) -> None:
    """Attach the shared backend-option flags to a benchmark CLI.

    Every figure accepts the same two knobs so Pallas variants can be swept
    without code edits (they flow into ``SweepSpec.options`` and from there
    into ``get_runtime(name, **options)``):

      --pallas             shorthand for use_pallas=True (per-body kernels)
      --backend-options    JSON dict of raw runtime options, e.g.
                           '{"combine": "onehot", "unroll": 2}' or
                           '{"steps_per_launch": 8}' (pallas_step temporal
                           blocking; "auto" = VMEM tuner)
    """
    ap.add_argument("--pallas", action="store_true",
                    help="use the Pallas task-body kernels (use_pallas=True)")
    ap.add_argument("--backend-options", default=None, metavar="JSON",
                    help="extra runtime options as a JSON dict")


def parse_backend_options(args: argparse.Namespace) -> Dict:
    """Decode --backend-options and fold --pallas in: the final options dict."""
    if getattr(args, "backend_options", None):
        opts = json.loads(args.backend_options)
        if not isinstance(opts, dict):
            raise SystemExit(
                f"--backend-options must be a JSON object, got {opts!r}")
    else:
        opts = {}
    if getattr(args, "pallas", False):
        opts["use_pallas"] = True
    return opts


def write_csv(name: str, header: Sequence[str], rows: Sequence[Sequence]):
    path = bench_path(name)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path


def fmt_us(v: Optional[float]) -> str:
    return "unreached" if v is None else f"{v:.1f}"
