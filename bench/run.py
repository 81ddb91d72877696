"""Run one cell of the on-chip benchmark once, in this process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout root:
the cell's configuration file, its traffic file under ``bench/traffic/``
(whose ``driver`` names ``bench/drivers/<driver>.py``), its limits under
``bench/limits/<cell>.json``, and each per-layer metric's reader,
``bench/metrics/<metric>.py``. A new configuration, traffic mix or metric is
new files and new entries, with no edit here.

With ``--trace 0`` the last line of stdout carries the cell's end-to-end
metrics; with ``--trace 1`` a few graphs per rung run under the profiler and
it carries the per-layer metrics, the device's busy and window seconds and a
breakdown. The numbers compared for ``correct`` are printed beside their
limits as the last lines of stderr and under the result's last key. JAX must
find a TPU with the chips the cell asks for: otherwise the run exits 2 and
prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: fixed, inside the checkout: the cache key holds the path
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included) and how many compile events it saw, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str) -> dict:
    """The cell's entries and files, all found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(m):
        return workload in m.get("workloads", [workload])

    return dict(
        cell=cell, config=config, traffic=traffic,
        driver=BENCH / "drivers" / f"{traffic['driver']}.py",
        limits=json.loads((BENCH / "limits" / f"{workload}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


class Context:
    """What a driver gets: the cell's data, the chips, and the harness's
    clocks and profiler."""

    def __init__(self, jax, found, devices, args, meter):
        self.jax = jax
        self.meter = meter
        self.config = found["config"]
        self.traffic = found["traffic"]
        self.limits = found["limits"]
        self.chips = found["cell"]["chips"]
        self.devices = devices
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.t_setup = None
        self.marks = {}
        self.compile_s = 0.0
        self.compiles_in_window = 0

    def say(self, line: str) -> None:
        print(line, flush=True)

    def mark(self, phase: str) -> None:
        """Seconds since process start at the end of a set-up phase."""
        self.marks[phase] = time.perf_counter() - T0

    def setup_done(self) -> None:
        self.t_setup = time.perf_counter()
        self.compile_s = self.meter.seconds
        self._events = self.meter.events

    def window_done(self) -> None:
        self.compiles_in_window = self.meter.events - self._events

    @contextlib.contextmanager
    def profile(self, label: str):
        """A profiler session; ``box.trace`` holds its reduction after."""
        d = TRACE_DIR / label
        shutil.rmtree(d, ignore_errors=True)
        box = types.SimpleNamespace(trace=None)
        self.jax.profiler.start_trace(str(d))
        try:
            yield box
        finally:
            self.jax.profiler.stop_trace()
        files = sorted(d.glob("plugins/profile/*/*.xplane.pb"))
        if files:
            box.trace = trace_reduce.load(files[-1])
        shutil.rmtree(d, ignore_errors=True)


def enable_compile_cache(jax) -> str:
    path = os.environ.get(CACHE_ENV) or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_devices(jax, chips: int, require_tpu: bool = True) -> list:
    """The first ``chips`` devices; NoChip where JAX finds no TPU or fewer
    chips."""
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def device_summary(record: dict):
    """(busy_s, window_s) over the traced rungs, busy averaged over chips."""
    busy = window = 0.0
    for rung in record["rungs"]:
        tr = rung["trace"]
        if tr is None or not tr.devices:
            continue
        busy += sum(trace_reduce.busy_ns(ops)
                    for ops in tr.devices.values()) / len(tr.devices) / 1e9
        window += rung["seconds"]
    return busy, window


def breakdown(record: dict, chips: int) -> dict:
    """The device ops that took most time and the longest idle gaps, with
    what the host was doing in each."""
    ops_s, gaps = {}, []
    for rung in record["rungs"]:
        tr = rung["trace"]
        if tr is None or not tr.devices:
            continue
        for ops in tr.devices.values():
            for o in ops:
                ops_s[o.kind] = ops_s.get(o.kind, 0.0) + o.dur_ns / 1e9 / chips
        first = min(tr.devices)
        for s, e in trace_reduce.idle_gaps(tr.devices[first])[:10]:
            gaps.append((f"grain {rung['grain']}: "
                         f"{trace_reduce.host_activity(tr.host, s, e)}",
                         (e - s) / 1e9))
    top = sorted(ops_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps, key=lambda g: -g[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in gaps]}


def run(args, *, found: dict = None, require_tpu: bool = True,
        cache: bool = True) -> dict:
    """One run of a cell; returns the result object. ``found`` replaces
    what ``resolve`` reads from ``BENCHMARK.json`` (tests run small sizes)."""
    if found is None:
        found = resolve(json.loads((ROOT / "BENCHMARK.json").read_text()),
                        args.workload)
    driver = load_module(found["driver"], f"bench_driver_{found['traffic']['driver']}")
    import jax

    if cache:
        enable_compile_cache(jax)
    t_import = time.perf_counter() - T0
    chips = found["cell"]["chips"]
    devices = find_devices(jax, chips, require_tpu)
    t_devices = time.perf_counter() - T0
    print(f"bench: {found['cell']['name']} on {chips} x "
          f"{devices[0].device_kind}, seed {args.seed}", flush=True)
    ctx = Context(jax, found, devices, args, CompileMeter(jax))
    ctx.marks.update(import_jax=t_import, tpu_init=t_devices)
    out = driver.run(ctx)
    setup_s = ctx.t_setup - T0
    print(f"bench: setup_s={setup_s!r} compile_s={ctx.compile_s!r} "
          f"compiles_in_window={ctx.compiles_in_window}", flush=True)
    print("bench: set-up phases end at (s) " + " ".join(
        f"{k}={v:.3f}" for k, v in ctx.marks.items()), flush=True)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if not ctx.trace:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in found["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        record = dict(out["record"], compile_s=ctx.compile_s,
                      device_kind=devices[0].device_kind)
        for m in found["per_layer"]:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"], device["window_s"] = device_summary(record)
        result["breakdown"] = breakdown(record, chips)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out["checks"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
