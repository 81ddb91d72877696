"""CI regression suite for the pallas_step smoke benchmark (reframe-style).

Earlier revisions hard-coded ONE rule (wall-per-step ratio vs a committed
baseline). This is now a parameterized suite in the style of a ReFrame
test battery: every check is a :class:`PerfCheck` with

  sanity    preconditions on the artifact (field present, value finite and
            positive) — a malformed run FAILS rather than silently passing;
  perf      the measured value judged against a per-system REFERENCE value
            within an allowed factor;
  health    an optional IN-RUN signal that distinguishes "the fast path
            degraded" from "the runner is slow".

Cross-machine wall-clock comparisons are inherently shaky (the committed
baseline was produced on the dev container; shared CI runners drift), so
an absolute regression alone never fails a check: it must coincide with
the run's own health signal collapsing. The failure mode this guard
exists for — the blocked/pipelined fast path silently degrading to
per-step dispatch (tuner collapsing to S=1, pipeline gating itself off,
an accidental per-step dispatch) — produces exactly that signature:
wall/step jumps 5-30x AND deep launches stop beating S=1 (or, for the
butterfly rows, pallas_step falls above fused in the same process), both
far outside runner variance. A uniformly slow runner keeps the in-run
signals healthy and only WARNs.

Per-system reference values: by default each check's reference is the
committed baseline's measured value, but the baseline JSON may carry a
``"references"`` object overriding reference and/or factor per check
name::

    "references": {"floor@64": {"reference": 5.0e-05, "factor": 3.0}}

so a platform with known-different floors tunes individual checks without
touching the guard. The optional ``--cost-model`` file (written by the CI
calibration step, ``python -m repro.kernels.probes --smoke``) adds sanity
checks over the measured CostModel — schema loads, probed costs positive
and finite — so a broken calibration fails CI before it silently steers
every "auto" schedule; a missing file SKIPs (local runs stay green).

The optional ``--trace`` file (written by benchmarks/overhead_decomposition)
arms the trace leg: a schema sanity check over the decomposition artifact
(``--smoke`` points it at the smoke artifact).

Exit status: 1 iff any check FAILs. Checks found in only one artifact are
reported and SKIPped, never judged.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
from typing import Callable, Dict, List, Optional

OK, WARN, FAIL, SKIP = "OK", "WARN", "FAIL", "SKIP"


def _us(v: float) -> str:
    return f"{v * 1e6:.2f} us/step"


@dataclasses.dataclass
class PerfCheck:
    """One parameterized check: sanity + perf-vs-reference + health.

    ``health_bad`` returns True when the in-run signal says the fast path
    itself degraded (not the runner); with no health signal available an
    absolute regression stays a WARN — same conservatism as always.
    """

    name: str
    value: Optional[float]
    reference: Optional[float]
    factor: float
    fmt: Callable[[float], str] = _us
    health_desc: str = ""
    health_value: Optional[float] = None
    health_bad: Optional[Callable[[float], bool]] = None
    sanity_errors: List[str] = dataclasses.field(default_factory=list)

    def evaluate(self) -> "CheckResult":
        if self.sanity_errors:
            return CheckResult(self.name, FAIL,
                               "sanity: " + "; ".join(self.sanity_errors))
        if self.value is None and self.reference is None:
            return CheckResult(self.name, OK, "sanity checks passed")
        if self.value is None:
            return CheckResult(self.name, SKIP,
                               "missing from current run (not judged)")
        if self.reference is None:
            return CheckResult(self.name, SKIP,
                               "no reference value (not judged)")
        ratio = self.value / self.reference
        detail = (f"reference {self.fmt(self.reference)}, current "
                  f"{self.fmt(self.value)} ({ratio:.2f}x, limit "
                  f"{self.factor:g}x)")
        if self.health_value is not None:
            detail += f", {self.health_desc}={self.health_value:.2f}"
        if ratio <= self.factor:
            return CheckResult(self.name, OK, detail)
        unhealthy = (self.health_bad is not None
                     and self.health_value is not None
                     and self.health_bad(self.health_value))
        if unhealthy:
            return CheckResult(
                self.name, FAIL,
                detail + " AND the in-run health signal collapsed — the "
                "fast path degraded, not the runner")
        return CheckResult(
            self.name, WARN,
            detail + " — SLOW-RUNNER? (absolute regression, in-run "
            "signal healthy)")


@dataclasses.dataclass
class CheckResult:
    name: str
    status: str
    message: str

    def line(self) -> str:
        return f"floor_guard: {self.name}: {self.message} [{self.status}]"


def _reference_for(baseline: dict, name: str, measured: Optional[float],
                   default_factor: float):
    """(reference, factor) for one check: the committed baseline's measured
    value unless its "references" object pins a per-system override."""
    override = baseline.get("references", {}).get(name, {})
    ref = override.get("reference", measured)
    factor = float(override.get("factor", default_factor))
    return ref, factor


def _sane_positive(name: str, value) -> List[str]:
    if value is None:
        return []  # absence is SKIP territory, not a sanity failure
    try:
        v = float(value)
    except (TypeError, ValueError):
        return [f"{name} is not a number: {value!r}"]
    if not math.isfinite(v) or v <= 0:
        return [f"{name} must be finite and positive, got {v!r}"]
    return []


def floor_checks(current: dict, baseline: dict, factor: float,
                 min_amortization: float) -> List[PerfCheck]:
    """Per-width headline-floor checks; health = the run's own S1/S8
    amortization (a degraded fast path measures ~1.0x, a healthy noisy
    run 1.3-9x)."""
    checks: List[PerfCheck] = []
    cur = current.get("floor_wall_per_step", {})
    base = baseline.get("floor_wall_per_step", {})
    speedups = current.get("s1_over_s8_speedup", {})
    for width, b in sorted(base.items(), key=lambda kv: int(kv[0])):
        name = f"floor@{width}"
        value = cur.get(width)
        ref, fac = _reference_for(baseline, name, b, factor)
        amort = speedups.get(width)
        checks.append(PerfCheck(
            name=name, value=value, reference=ref, factor=fac,
            health_desc="S1/S8", health_value=amort,
            health_bad=lambda a, lo=min_amortization: a < lo,
            sanity_errors=_sane_positive(name, value),
        ))
    return checks


def butterfly_checks(current: dict, baseline: dict,
                     factor: float) -> List[PerfCheck]:
    """Butterfly (stride-plan) floor checks; health = the run's own
    pallas/fused ratio — the stride plan degrading pushes pallas_step
    ABOVE fused in the same process, which runner slowness cannot."""
    checks: List[PerfCheck] = []
    cur = current.get("butterfly_floor_wall_per_step", {})
    base = baseline.get("butterfly_floor_wall_per_step", {})
    ratios = current.get("butterfly_over_fused_per_step", {})
    for key, b in sorted(base.items()):
        name = f"butterfly@{key}"
        pattern, width = key.split("@")
        value = cur.get(key)
        ref, fac = _reference_for(baseline, name, b, factor)
        in_run = ratios.get(pattern, {}).get(width)
        checks.append(PerfCheck(
            name=name, value=value, reference=ref, factor=fac,
            health_desc="pallas/fused", health_value=in_run,
            health_bad=lambda r: r > 1.0,
            sanity_errors=_sane_positive(name, value),
        ))
    return checks


def cost_model_checks(model_file: dict) -> List[PerfCheck]:
    """Sanity-only checks over the CI calibration artifact: every probed
    cost must be finite and positive (perf bounds don't apply — the model
    is measured fresh per runner; what must never happen is a garbage
    calibration silently steering every "auto" schedule)."""
    checks: List[PerfCheck] = []
    entries = model_file.get("entries", {})
    if not isinstance(entries, dict) or not entries:
        return [PerfCheck(name="cost_model", value=None, reference=None,
                          factor=1.0,
                          sanity_errors=["calibration file has no entries"])]
    for key, m in sorted(entries.items()):
        errors: List[str] = []
        for field in ("exchange_row_steps", "launch_us", "row_step_us"):
            errors += _sane_positive(field, m.get(field, None))
            if m.get(field) is None:
                errors.append(f"{field} missing")
        for group in ("halo_exchange_us", "stride_exchange_us", "gather_us"):
            for k, v in (m.get(group) or {}).items():
                errors += _sane_positive(f"{group}[{k}]", v)
        if m.get("source") != "measured":
            errors.append(f"source is {m.get('source')!r}, not 'measured'")
        checks.append(PerfCheck(
            name=f"cost_model[{key}]", value=None, reference=None,
            factor=1.0, sanity_errors=errors))
        if not errors:
            # a sane model SKIPs the perf leg by construction (no
            # reference); surface the calibration in the CI log instead
            print(f"floor_guard: cost_model[{key}]: exchange="
                  f"{float(m['exchange_row_steps']):.0f} row-steps, "
                  f"launch={m['launch_us']:.1f}us, "
                  f"row-step={m['row_step_us']:.4f}us")
    return checks


def trace_checks(trace_art: dict) -> List[PerfCheck]:
    """Trace leg over the overhead_decomposition artifact: ``trace@schema``
    checks the artifact's schema."""
    errors: List[str] = []
    if trace_art.get("schema") != 1:
        errors.append(
            f"trace artifact schema {trace_art.get('schema')!r}, expected 1")
    return [PerfCheck(name="trace@schema", value=None, reference=None,
                      factor=1.0, sanity_errors=errors)]


def chaos_checks(chaos_art: dict, *, max_recovery_tax: float,
                 max_armor_tax: float) -> List[PerfCheck]:
    """Resilience leg over the benchmarks/chaos artifact.

    ``chaos@schema`` is the sanity half (artifact schema, rows judged,
    verdict present); ``chaos@identity`` fails outright when any chaos row
    lost bit-identical recovery — that IS the in-run correctness signal,
    and a correctness loss is never a slow-runner artifact. The per-class
    ``chaos@tax:*`` checks then apply the standard two-signal rule to the
    recovery tax: tax past the bound with bit-identity intact is a WARN
    (loaded runner stretching the backoff sleeps); tax past the bound with
    identity broken FAILs. ``chaos@armor`` bounds what the resilient
    executor costs with no faults at all (the zero-cost contract on the
    clean path)."""
    errors: List[str] = []
    if chaos_art.get("schema") != SCHEMA_CHAOS:
        errors.append(
            f"chaos artifact schema {chaos_art.get('schema')!r}, "
            f"expected {SCHEMA_CHAOS}")
    verdict = chaos_art.get("verdict") or {}
    judged = [r for r in chaos_art.get("rows", []) if "skip" not in r]
    if not judged:
        errors.append("chaos artifact judged no rows")
    if "recovery_bit_identical" not in verdict:
        errors.append("verdict missing recovery_bit_identical")
    checks = [PerfCheck(name="chaos@schema", value=None, reference=None,
                        factor=1.0, sanity_errors=errors)]
    identity_errors = [] if verdict.get("recovery_bit_identical", True) \
        else ["a faulted run was NOT bit-identical after recovery"]
    checks.append(PerfCheck(name="chaos@identity", value=None,
                            reference=None, factor=1.0,
                            sanity_errors=identity_errors))
    fmt = lambda v: f"{v:.2f}x tax"  # noqa: E731
    for cls, summary in sorted((verdict.get("per_class") or {}).items()):
        if cls == "straggler":
            # the straggler row's wall carries a deliberate stall sized to
            # the run (a detection row, not a recovery row): its tax is
            # ~3x by construction and proves nothing about recovery cost
            continue
        health = 1.0 if summary.get("bit_identical") else 0.0
        checks.append(PerfCheck(
            name=f"chaos@tax:{cls}",
            value=summary.get("max_recovery_tax"), reference=1.0,
            factor=max_recovery_tax, fmt=fmt,
            health_desc="bit_identical", health_value=health,
            health_bad=lambda h: h < 1.0,
            sanity_errors=_sane_positive(
                f"chaos@tax:{cls}", summary.get("max_recovery_tax")),
        ))
    identity_health = 1.0 if verdict.get("recovery_bit_identical") else 0.0
    checks.append(PerfCheck(
        name="chaos@armor", value=verdict.get("max_armor_tax"),
        reference=1.0, factor=max_armor_tax, fmt=fmt,
        health_desc="bit_identical", health_value=identity_health,
        health_bad=lambda h: h < 1.0,
        sanity_errors=_sane_positive("chaos@armor",
                                     verdict.get("max_armor_tax")),
    ))
    return checks


SCHEMA_CHAOS = 1


def scaling_checks(scaling_art: dict, scaling_base: dict, factor: float, *,
                   max_pallas_over_bsp: float,
                   min_gather_speedup: float) -> List[PerfCheck]:
    """Scaling leg over the fig2_scaling artifact (weak/strong sweeps).

    ``scaling@schema`` is the sanity half: the guard block exists and its
    efficiencies are in range. ``scaling@weak`` judges the weak-scaling
    OVERHEAD GROWTH at the guard device count — 1/efficiency, lower is
    better, so the standard ratio-vs-reference machinery applies — against
    the committed baseline, with the run's OWN pallas/bsp wall-per-task
    ratio at the same D as the health signal: the megakernel pricing tasks
    like per-step-dispatch bsp in the same process is a fast-path
    collapse, which runner slowness cannot produce (both walls stretch
    together). ``scaling@gather`` bounds the chunked-vs-monolithic gather
    ablation at D >= 16: the walls come from ONE worker process, so the
    ratio is already machine-independent and the health signal is the
    speedup itself. Smoke artifacts cap at D=8 and carry no 16+ ablation —
    that check SKIPs, the weak check still judges at the smoke guard D.
    A baseline produced at a different guard D yields no reference
    (SKIP): efficiency at D=8 says nothing about the D=16 bar.
    """
    errors: List[str] = []
    guard = scaling_art.get("guard") or {}
    if not guard:
        errors.append("scaling artifact has no guard block")
    eff = guard.get("weak_efficiency")
    if eff is not None and not (0.0 < float(eff) <= 2.0):
        errors.append(f"weak_efficiency out of (0, 2]: {eff!r}")
    errors += _sane_positive("guard_devices", guard.get("guard_devices"))
    checks = [PerfCheck(name="scaling@schema", value=None, reference=None,
                        factor=1.0, sanity_errors=errors)]

    base_guard = scaling_base.get("guard") or {}
    value = None if eff is None else 1.0 / max(float(eff), 1e-9)
    base_eff = base_guard.get("weak_efficiency")
    measured_ref = None
    if (base_eff is not None
            and base_guard.get("guard_devices") == guard.get("guard_devices")):
        measured_ref = 1.0 / max(float(base_eff), 1e-9)
    weak_name = f"scaling@weak:D{guard.get('guard_devices', '?')}"
    ref, fac = _reference_for(scaling_base, weak_name, measured_ref, factor)
    pallas = guard.get("pallas_wall_per_task_us")
    bsp = guard.get("bsp_wall_per_task_us")
    in_run = None
    if pallas is not None and bsp:
        in_run = float(pallas) / float(bsp)
    checks.append(PerfCheck(
        name=weak_name, value=value, reference=ref, factor=fac,
        fmt=lambda v: f"{v:.2f}x overhead growth",
        health_desc="pallas/bsp", health_value=in_run,
        health_bad=lambda r, hi=max_pallas_over_bsp: r > hi,
        sanity_errors=_sane_positive("weak overhead growth", value),
    ))

    speedup = guard.get("chunked_speedup_at_16plus")
    checks.append(PerfCheck(
        name="scaling@gather",
        value=None if speedup is None else 1.0 / max(float(speedup), 1e-9),
        reference=1.0, factor=1.0 / min_gather_speedup,
        fmt=lambda v: f"chunked at {1.0 / v:.2f}x vs monolithic",
        health_desc="in-run speedup", health_value=speedup,
        health_bad=lambda s, lo=min_gather_speedup: s < lo,
    ))
    return checks


SCHEMA_SERVE = 1


def serve_checks(serve_art: dict, serve_base: dict, factor: float, *,
                 min_slot_utilization: float = 0.5) -> List[PerfCheck]:
    """Serving leg over the benchmarks/serve_taskbench artifact.

    ``serve@schema`` is the sanity half; ``serve@identity`` fails outright
    when any served request lost bit-identity against its serial oracle —
    correctness, never a slow-runner artifact. ``serve@churn`` likewise
    fails outright when the continuous-batching contract degraded: no
    stacked cohort changed membership >= 2 times with zero recompiles, or
    the packer collapsed the mixed stream below two stacked cohorts —
    both are structural properties of the fabric, independent of runner
    speed. The per-K ``serve@p99:*`` checks then apply the standard
    two-signal rule to tail latency vs the committed baseline: a p99
    regression alone WARNs (loaded runner stretches every wall); it FAILs
    only when that row's in-run slot utilization ALSO cratered — idle
    slots with slow requests mean admission/packing broke, which runner
    slowness cannot produce (a slow runner keeps slots exactly as busy)."""
    errors: List[str] = []
    if serve_art.get("schema") != SCHEMA_SERVE:
        errors.append(
            f"serve artifact schema {serve_art.get('schema')!r}, "
            f"expected {SCHEMA_SERVE}")
    verdict = serve_art.get("verdict") or {}
    rows = [r for r in serve_art.get("rows", []) if "skip" not in r]
    if not rows:
        errors.append("serve artifact judged no rows")
    for key in ("bit_identical", "dynamic_cohort", "min_stacked_cohorts"):
        if key not in verdict:
            errors.append(f"verdict missing {key}")
    checks = [PerfCheck(name="serve@schema", value=None, reference=None,
                        factor=1.0, sanity_errors=errors)]
    identity_errors = [] if verdict.get("bit_identical", True) \
        else ["a served request was NOT bit-identical to its serial oracle"]
    checks.append(PerfCheck(name="serve@identity", value=None,
                            reference=None, factor=1.0,
                            sanity_errors=identity_errors))
    churn_errors = []
    if not verdict.get("dynamic_cohort", True):
        churn_errors.append(
            "no stacked cohort churned membership >= 2 times without a "
            "recompile (continuous batching degraded to static cohorts)")
    if verdict.get("min_stacked_cohorts", 2) < 2:
        churn_errors.append(
            "mixed request stream produced < 2 stacked cohorts (packer "
            "collapsed compatibility classes)")
    checks.append(PerfCheck(name="serve@churn", value=None, reference=None,
                            factor=1.0, sanity_errors=churn_errors))
    base_p99 = (serve_base.get("verdict") or {}).get("p99_ms_by_slots", {})
    fmt = lambda v: f"{v:.1f} ms p99"  # noqa: E731
    for row in rows:
        k = str(row.get("slots"))
        name = f"serve@p99:K{k}"
        ref, fac = _reference_for(serve_base, name, base_p99.get(k), factor)
        checks.append(PerfCheck(
            name=name, value=row.get("p99_ms"), reference=ref, factor=fac,
            fmt=fmt,
            health_desc="slot_utilization",
            health_value=row.get("slot_utilization"),
            health_bad=lambda u, lo=min_slot_utilization: u < lo,
            sanity_errors=_sane_positive(name, row.get("p99_ms")),
        ))
    return checks


def build_suite(current: dict, baseline: dict, factor: float,
                min_amortization: float,
                cost_model: Optional[dict] = None,
                trace_art: Optional[dict] = None,
                chaos_art: Optional[dict] = None,
                max_recovery_tax: float = 2.5,
                max_armor_tax: float = 3.0,
                scaling_art: Optional[dict] = None,
                scaling_base: Optional[dict] = None,
                max_pallas_over_bsp: float = 1.5,
                min_gather_speedup: float = 0.9,
                serve_art: Optional[dict] = None,
                serve_base: Optional[dict] = None,
                min_slot_utilization: float = 0.5) -> List[PerfCheck]:
    checks = floor_checks(current, baseline, factor, min_amortization)
    checks += butterfly_checks(current, baseline, factor)
    if cost_model is not None:
        checks += cost_model_checks(cost_model)
    if trace_art is not None:
        checks += trace_checks(trace_art)
    if chaos_art is not None:
        checks += chaos_checks(chaos_art, max_recovery_tax=max_recovery_tax,
                               max_armor_tax=max_armor_tax)
    if scaling_art is not None:
        checks += scaling_checks(scaling_art, scaling_base or {}, factor,
                                 max_pallas_over_bsp=max_pallas_over_bsp,
                                 min_gather_speedup=min_gather_speedup)
    if serve_art is not None:
        checks += serve_checks(serve_art, serve_base or {}, factor,
                               min_slot_utilization=min_slot_utilization)
    return checks


def run_suite(checks: List[PerfCheck],
              families: Dict[str, int]) -> List[str]:
    """Evaluate every check, print the table, return FAIL messages.

    ``families`` maps a check-name prefix to the minimum number of JUDGED
    (non-SKIP) checks the suite must contain for it — a baseline full of
    floors that the current run judged none of is itself a failure
    (schema drift / rows silently missing), the "sanity" half of the
    reframe contract applied to the suite as a whole."""
    failures: List[str] = []
    judged: Dict[str, int] = {k: 0 for k in families}
    for c in checks:
        res = c.evaluate()
        print(res.line())
        if res.status == FAIL:
            failures.append(f"{res.name}: {res.message}")
        if res.status not in (SKIP,):
            for prefix in families:
                if res.name.startswith(prefix):
                    judged[prefix] += 1
    for prefix, minimum in families.items():
        if judged[prefix] < minimum:
            failures.append(
                f"suite judged {judged[prefix]} {prefix}* checks, needs "
                f">= {minimum} (rows missing or key schema drifted)")
    return failures


def check(current: dict, baseline: dict, factor: float,
          min_amortization: float,
          cost_model: Optional[dict] = None,
          trace_art: Optional[dict] = None,
          chaos_art: Optional[dict] = None,
          max_recovery_tax: float = 2.5,
          max_armor_tax: float = 3.0,
          scaling_art: Optional[dict] = None,
          scaling_base: Optional[dict] = None,
          max_pallas_over_bsp: float = 1.5,
          min_gather_speedup: float = 0.9,
          serve_art: Optional[dict] = None,
          serve_base: Optional[dict] = None,
          min_slot_utilization: float = 0.5) -> list:
    """Returns a list of human-readable failures (empty = pass)."""
    base = baseline.get("floor_wall_per_step", {})
    if not base:
        return ["baseline has no floor_wall_per_step field"]
    families = {"floor@": 1}
    if baseline.get("butterfly_floor_wall_per_step"):
        # baselines that predate the butterfly rows carry no keys: nothing
        # to guard (regenerating the baseline arms this family)
        families["butterfly@"] = 1
    if trace_art is not None:
        families["trace@"] = 1
    if chaos_art is not None:
        families["chaos@"] = 2
    if scaling_art is not None:
        families["scaling@"] = 1
    if serve_art is not None:
        # schema + identity + churn always judge; p99 rows may SKIP when
        # the committed baseline predates a new K sweep
        families["serve@"] = 3
    suite = build_suite(current, baseline, factor, min_amortization,
                        cost_model, trace_art, chaos_art,
                        max_recovery_tax, max_armor_tax,
                        scaling_art, scaling_base,
                        max_pallas_over_bsp, min_gather_speedup,
                        serve_art, serve_base, min_slot_utilization)
    return run_suite(suite, families)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--current",
                    default="artifacts/bench/pallas_floor_smoke.json")
    ap.add_argument("--baseline",
                    default="artifacts/bench/pallas_floor_smoke_baseline.json")
    ap.add_argument("--factor", type=float, default=2.0,
                    help="default max current/reference ratio (per-check "
                         "overrides live in the baseline's 'references')")
    ap.add_argument("--min-amortization", type=float, default=1.05,
                    help="in-run S1/S8 speedup below which an absolute "
                         "regression counts as a fast-path failure")
    ap.add_argument("--cost-model", default=None,
                    help="CI calibration artifact to sanity-check "
                         "(missing file = skip, stays green locally)")
    ap.add_argument("--trace", default=None,
                    help="overhead_decomposition artifact feeding the "
                         "trace health leg (missing file = skip)")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke defaults: --trace points at the smoke "
                         "decomposition artifact")
    ap.add_argument("--chaos", default=None, nargs="?",
                    const="artifacts/bench/chaos.json",
                    help="benchmarks/chaos artifact feeding the resilience "
                         "leg (flag alone uses the default path; missing "
                         "file = skip)")
    ap.add_argument("--max-recovery-tax", type=float, default=2.5,
                    help="faulted/clean resilient wall ratio above which "
                         "a chaos tax check regresses (two-signal: WARN "
                         "unless bit-identity also broke)")
    ap.add_argument("--max-armor-tax", type=float, default=3.0,
                    help="no-fault resilient/production wall ratio bound "
                         "(the clean-path cost of the armor)")
    ap.add_argument("--scaling", default=None, nargs="?",
                    const="artifacts/bench/fig2_scaling.json",
                    help="fig2_scaling artifact feeding the scaling@ leg "
                         "(flag alone uses the full-run path; under "
                         "--smoke the bare flag points at the smoke "
                         "artifact; missing file = skip)")
    ap.add_argument("--scaling-baseline",
                    default="artifacts/bench/fig2_scaling_baseline.json",
                    help="committed scaling baseline (guard references; "
                         "missing file = references only from overrides)")
    ap.add_argument("--max-pallas-over-bsp", type=float, default=1.5,
                    help="in-run health bound: pallas_step/bsp "
                         "wall-per-task ratio at the guard D above which "
                         "a weak-efficiency regression FAILs")
    ap.add_argument("--min-gather-speedup", type=float, default=0.9,
                    help="chunked/monolithic gather speedup at D>=16 "
                         "below which the ablation check FAILs (in-run "
                         "ratio, no slow-runner escape)")
    ap.add_argument("--serve", default=None, nargs="?",
                    const="artifacts/bench/serve_taskbench.json",
                    help="benchmarks/serve_taskbench artifact feeding the "
                         "serving leg (flag alone uses the default path; "
                         "missing file = skip)")
    ap.add_argument("--serve-baseline",
                    default="artifacts/bench/serve_taskbench_baseline.json",
                    help="committed serving baseline (p99 references; "
                         "missing file = references only from overrides)")
    ap.add_argument("--min-slot-utilization", type=float, default=0.5,
                    help="in-run health bound: slot utilization below "
                         "which a p99 regression FAILs (idle slots + slow "
                         "requests = admission broke, not the runner)")
    a = ap.parse_args(argv)
    trace_path = a.trace
    if trace_path is None and a.smoke:
        trace_path = "artifacts/bench/overhead_decomposition_smoke.json"
    with open(a.current) as f:
        current = json.load(f)
    with open(a.baseline) as f:
        baseline = json.load(f)
    cost_model = None
    if a.cost_model:
        try:
            with open(a.cost_model) as f:
                cost_model = json.load(f)
        except FileNotFoundError:
            print(f"floor_guard: cost model {a.cost_model} absent "
                  f"(calibration checks skipped)")
    trace_art = None
    if trace_path:
        try:
            with open(trace_path) as f:
                trace_art = json.load(f)
        except FileNotFoundError:
            print(f"floor_guard: trace artifact {trace_path} absent "
                  f"(trace health leg skipped)")
    chaos_art = None
    if a.chaos:
        try:
            with open(a.chaos) as f:
                chaos_art = json.load(f)
        except FileNotFoundError:
            print(f"floor_guard: chaos artifact {a.chaos} absent "
                  f"(resilience leg skipped)")
    scaling_path = a.scaling
    if scaling_path == "artifacts/bench/fig2_scaling.json" and a.smoke:
        scaling_path = "artifacts/bench/fig2_scaling_smoke.json"
    scaling_art = scaling_base = None
    if scaling_path:
        try:
            with open(scaling_path) as f:
                scaling_art = json.load(f)
        except FileNotFoundError:
            print(f"floor_guard: scaling artifact {scaling_path} absent "
                  f"(scaling@ leg skipped)")
        if scaling_art is not None:
            try:
                with open(a.scaling_baseline) as f:
                    scaling_base = json.load(f)
            except FileNotFoundError:
                print(f"floor_guard: scaling baseline {a.scaling_baseline} "
                      f"absent (scaling@weak judged only via overrides)")
    serve_art = serve_base = None
    if a.serve:
        try:
            with open(a.serve) as f:
                serve_art = json.load(f)
        except FileNotFoundError:
            print(f"floor_guard: serve artifact {a.serve} absent "
                  f"(serving leg skipped)")
        if serve_art is not None:
            try:
                with open(a.serve_baseline) as f:
                    serve_base = json.load(f)
            except FileNotFoundError:
                print(f"floor_guard: serve baseline {a.serve_baseline} "
                      f"absent (serve@p99 judged only via overrides)")
    failures = check(current, baseline, a.factor, a.min_amortization,
                     cost_model, trace_art, chaos_art,
                     a.max_recovery_tax, a.max_armor_tax,
                     scaling_art, scaling_base,
                     a.max_pallas_over_bsp, a.min_gather_speedup,
                     serve_art, serve_base, a.min_slot_utilization)
    for msg in failures:
        print(f"floor_guard: FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
