"""Per-backend overhead decomposition from span traces (DESIGN.md §10).

The paper decomposes each system's wall into what the runtime spends
(dispatch, communication) versus what the application gets (compute); this
benchmark produces that figure for OUR backend ladder from the span
tracer: every backend runs every pattern with ``trace=`` on, and each
row's wall is attributed to dispatch / exchange / gather / compute / idle
by interval arithmetic over the recorded spans (repro.obs.decompose).

Each row carries the stacked per-category breakdown (the figure's bars)
— e.g. `serialized` should be dispatch-dominated at fine grain while
`bsp_scan`/`fused` collapse everything into one dispatch. pallas_step runs
one scanned program per graph, so its row splits only host dispatch from
the device drain; per-op device time comes from a profiler trace
(DESIGN.md §10).

Full mode (default): 4 devices, width 512, tuned ("auto") launch depth.
Smoke mode: 2 devices, width 64, explicit steps_per_launch=4 (the
analytic covering rule declines tiny shapes, so smoke FORCES the
pipelined path).

Chrome traces for every row land in artifacts/bench/traces/ (load in
chrome://tracing or ui.perfetto.dev).
"""
from __future__ import annotations

import argparse
import json

from benchmarks.common import (
    SweepSpec,
    bench_path,
    parse_backend_options,
    backend_options_args,
    run_worker,
)

#: every backend in the ladder, fine-to-coarse dispatch granularity
BACKENDS = ("serialized", "bsp", "overlap", "pallas_step", "bsp_scan",
            "fused")

#: patterns every backend supports (overlap constrains the grid: it runs
#: halo patterns + random_nearest only)
PATTERNS = ("stencil_1d", "nearest")

#: extra pallas_step-only rows exercising the stride / all-gather plans
EXTRA_PLANS = ("fft", "spread")

CATEGORIES = ("dispatch", "exchange", "gather", "compute.boundary",
              "compute.interior", "idle")


def _trace_cell(row: dict) -> dict:
    tr = row.get("trace") or {}
    return {
        "wall": row.get("wall"),
        "dispatches": row.get("dispatches"),
        "wall_us": tr.get("wall_us"),
        "fractions": tr.get("fractions"),
        "categories_us": tr.get("categories_us"),
        "decisions": tr.get("decisions"),
    }


def run(devices: int, width: int, steps: int, grain: int, *,
        pallas_options: dict, options: dict, trace_dir: str,
        timeout: int = 3000) -> dict:
    decomposition: dict = {}
    for pattern in PATTERNS:
        cells: dict = {}
        # the five option-free backends share ONE worker (same device set,
        # same process — the cross-backend fractions are comparable)
        base = run_worker(SweepSpec(
            runtime="", pattern=pattern, devices=devices, width=width,
            steps=steps, grains=(grain,),
            compare_runtimes=tuple(b for b in BACKENDS if b != "pallas_step"),
            options=dict(options), trace=True, trace_dir=trace_dir,
        ), timeout=timeout)
        for row in base:
            if "skip" in row:
                cells[row["runtime"]] = {"skip": row["skip"]}
            else:
                cells[row["runtime"]] = _trace_cell(row)
        ps = run_worker(SweepSpec(
            runtime="pallas_step", pattern=pattern, devices=devices,
            width=width, steps=steps, grains=(grain,),
            options={**options, **pallas_options},
            trace=True, trace_dir=trace_dir,
        ), timeout=timeout)
        cells["pallas_step"] = (
            {"skip": ps[0]["skip"]} if "skip" in ps[0] else
            _trace_cell(ps[0]))
        decomposition[pattern] = cells
    extra: dict = {}
    for pattern in EXTRA_PLANS:
        rows = run_worker(SweepSpec(
            runtime="pallas_step", pattern=pattern, devices=devices,
            width=width, steps=steps, grains=(grain,),
            options=dict(options), trace=True, trace_dir=trace_dir,
        ), timeout=timeout)
        extra[pattern] = (
            {"skip": rows[0]["skip"]} if "skip" in rows[0] else
            _trace_cell(rows[0]))
    return {
        "schema": 1,
        "devices": devices,
        "width": width,
        "steps": steps,
        "grain": grain,
        "pallas_options": pallas_options,
        "decomposition": decomposition,
        "extra_plans": extra,
    }


def print_report(art: dict) -> None:
    for pattern, cells in list(art["decomposition"].items()) + [
            (f"pallas_step plan rows", art["extra_plans"])]:
        print(f"\n-- {pattern}: wall decomposition "
              f"(% of traced extent, D={art['devices']}, "
              f"W={art['width']}, T={art['steps']}, "
              f"grain={art['grain']}) --")
        hdr = f"{'backend':12s}" + "".join(
            f"{c.split('.')[-1]:>10s}" for c in CATEGORIES) + f"{'wall ms':>10s}"
        print(hdr)
        for name, cell in cells.items():
            if "skip" in cell:
                print(f"{name:12s}  skipped: {cell['skip']}")
                continue
            fr = cell.get("fractions") or {}
            bars = "".join(
                f"{100 * float(fr.get(c, 0.0)):>9.1f}%" for c in CATEGORIES)
            print(f"{name:12s}{bars}{1e3 * cell['wall']:>10.2f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (2 devices, forced S=4)")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--width", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--grain", type=int, default=None)
    ap.add_argument("--out", default=None)
    backend_options_args(ap)
    args = ap.parse_args(argv)
    options = parse_backend_options(args)

    if args.smoke:
        devices = args.devices or 2
        width = args.width or 64
        steps = args.steps or 9
        grain = args.grain or 64
        # the analytic covering rule declines tiny blocks; force the
        # pipelined path so CI still exercises it
        pallas_options = {"steps_per_launch": 4}
        out = args.out or bench_path("overhead_decomposition_smoke.json")
    else:
        devices = args.devices or 4
        width = args.width or 512
        steps = args.steps or 33
        grain = args.grain or 1024
        pallas_options = {"steps_per_launch": "auto"}
        out = args.out or bench_path("overhead_decomposition.json")

    art = run(devices, width, steps, grain, pallas_options=pallas_options,
              options=options, trace_dir=bench_path("traces"))
    art["mode"] = "smoke" if args.smoke else "full"
    with open(out, "w") as f:
        json.dump(art, f, indent=1)
    print_report(art)
    print(f"\nwrote {out} (chrome traces in {bench_path('traces')})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
