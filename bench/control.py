"""Readings for the limits of ``correct``: the program and its control.

    python3 bench/control.py --workload <cell> --first-seed <n> --seeds 12 \
        --control-seeds 3 --seconds 5

Sets up the cell once (as ``bench/run.py`` does), then for each seed makes
that seed's initial states, runs a short window at the cell's own sizes and
compares every output with the reference: the program's readings, from
which the lower end of each limit is set. For the first ``--control-seeds``
seeds it also puts the reference computed in the nearest precision below
the configuration's (bfloat16 for float32) in the program's place and
compares it the same way: the control's readings, which set the upper end.
Prints one line per seed and, last, one JSON object with the largest
program reading and the smallest control reading of each number.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as harness  # noqa: E402

NUMBERS = ("class_mismatch", "finite_rel_err")


def readings(args, *, found=None, require_tpu=True, cache=True) -> dict:
    if found is None:
        found = harness.resolve(
            json.loads((harness.ROOT / "BENCHMARK.json").read_text()),
            args.workload)
    driver = harness.load_module(
        found["driver"], f"bench_driver_{found['traffic']['driver']}")
    import jax

    if cache:
        harness.enable_compile_cache(jax)
    devices = harness.find_devices(jax, found["cell"]["chips"], require_tpu)
    ctx = harness.Context(
        jax, found, devices,
        types.SimpleNamespace(seed=args.first_seed, seconds=args.seconds,
                              trace=0),
        harness.CompileMeter(jax))
    lad, _ = driver.setup(ctx)
    program = {k: [] for k in NUMBERS}
    control = {k: [] for k in NUMBERS}
    for i in range(args.seeds):
        seed = args.first_seed + i
        inits = driver.make_inits(lad, seed)
        runs = driver.window(ctx, lad, inits, args.seconds, seed)
        refs = driver.references(lad, inits, lad.dtype)
        got = driver.check(lad, runs, refs)
        line = {"seed": seed, "compared": got["compared"],
                "program": {k: got[k] for k in NUMBERS}}
        for k in NUMBERS:
            program[k].append(got[k])
        if i < args.control_seeds:
            low = driver.control_check(lad, inits, refs)
            line["control"] = {k: low[k] for k in NUMBERS}
            for k in NUMBERS:
                control[k].append(low[k])
        print(json.dumps(line), flush=True)
        del runs, refs
    return {"workload": args.workload, "seeds": args.seeds,
            "program_max": {k: max(v) for k, v in program.items()},
            "control_min": {k: min(v) for k, v in control.items() if v}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        out = readings(args)
    except harness.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
