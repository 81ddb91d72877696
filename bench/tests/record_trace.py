"""Record the small chip trace that test_trace_reduce.py reads.

    python3 bench/tests/record_trace.py --chips 4 --out bench/tests/data

Runs one stencil_1d graph of STEPS steps at grain 1 over 4096 rows per
chip through ``pallas_step`` with default options, twice (the first call
compiles), with the profiler on around the second, and writes the trace as
``<out>/stencil_1d_x<chips>.xplane.pb``. On a TPU only.
"""
import argparse
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

STEPS = 6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", default=str(ROOT / "bench" / "tests" / "data"))
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    from repro.core.graph import TaskGraph
    from repro.core.runtimes.base import get_runtime
    from repro.core.task_kernels import KernelSpec, initial_state

    devices = jax.devices()[:args.chips]
    graph = TaskGraph(steps=STEPS, width=4096 * args.chips,
                      pattern="stencil_1d", payload=64,
                      kernel=KernelSpec("compute_bound", 1))
    fn = get_runtime("pallas_step", devices=devices).build(graph)
    init = initial_state(graph.width, graph.payload, 0)
    jax.block_until_ready(fn(init))
    with tempfile.TemporaryDirectory(dir=ROOT) as d:
        with jax.profiler.trace(d):
            jax.block_until_ready(fn(init))
        src = sorted(Path(d).glob("plugins/profile/*/*.xplane.pb"))[-1]
        Path(args.out).mkdir(parents=True, exist_ok=True)
        dst = Path(args.out) / f"stencil_1d_x{args.chips}.xplane.pb"
        shutil.copy(src, dst)
    print(f"record_trace: wrote {dst} ({dst.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
