"""``correct`` holds for the program and fails for its control and for each
fault of the timed path a cell can have.

Each test drives a whole run of a cell (``bench/run.py``'s ``run``) at a
small size on the CPU, past the harness's look for a chip, and with the
timed path broken underneath where a fault is planted: a program that
returns its state unchanged, one that leaves half of the rows out, one
that alters an answer where it is produced, and, on four host devices, one
whose ring halo exchange is left out. The control is the float32
reference's bfloat16 twin in the program's place (``bench/control.py``).
"""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import control
from bench import run as harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
WIDTH, STEPS = 128, 40  # sources sit in [STEPS, W - STEPS) per chip count


def small(cell, width=WIDTH):
    found = harness.resolve(SPEC, cell)
    found["config"] = dict(found["config"], points_per_chip=width,
                           steps=STEPS)
    found["traffic"] = dict(found["traffic"], grains=[1, 4])
    return found


def small_x4(width):
    """The stencil cell's configuration on four chips, which no cell runs
    yet."""
    found = small("stencil_1d.ladder", width)
    found["cell"] = dict(found["cell"], name="stencil_1d.ladder.x4", chips=4)
    return found


def run_cell(cell, *, trace=0, found=None):
    args = types.SimpleNamespace(workload=cell, seed=2**33 + 7,
                                 seconds=0.4, trace=trace)
    return harness.run(args, found=found or small(cell), require_tpu=False,
                       cache=False)


def break_program(monkeypatch, fault):
    """Wrap every built program so ``fault(out, init)`` is what it returns."""
    from repro.core.runtimes import pallas_step

    build = pallas_step.PallasStepRuntime.build

    def broken(self, graph):
        fn = build(self, graph)
        return lambda init: fault(fn(init), init)

    monkeypatch.setattr(pallas_step.PallasStepRuntime, "build", broken)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_program_is_correct(cell):
    res = run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["class_mismatch"]["value"] == 0


def test_traced_run_is_correct_and_reports_the_device():
    res = run_cell("stencil_1d.ladder", trace=1)
    assert res["correct"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "compile_s" in res["metrics"]


def _unchanged(out, init):
    return init


def _half_rows(out, init):
    import jax.numpy as jnp

    h = out.shape[0] // 2
    return jnp.concatenate([out[:h], init[h:]])


def _altered(out, init):
    return out.at[0].add(0.5)  # row 0 is outside every light cone


@pytest.mark.parametrize("fault", [_unchanged, _half_rows, _altered],
                         ids=["unchanged", "half_rows", "altered"])
@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_fault_is_not_correct(monkeypatch, cell, fault):
    break_program(monkeypatch, fault)
    res = run_cell(cell)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def test_control_fails_and_program_passes():
    args = types.SimpleNamespace(workload="stencil_1d.ladder", first_seed=5,
                                 seeds=2, control_seeds=2, seconds=0.4)
    out = control.readings(args, found=small("stencil_1d.ladder"),
                           require_tpu=False, cache=False)
    limits = harness.resolve(SPEC, "stencil_1d.ladder")["limits"]
    prog, ctrl = out["program_max"], out["control_min"]
    assert prog["class_mismatch"] <= limits["class_mismatch"]
    assert prog["finite_rel_err"] <= limits["finite_rel_err"]
    assert ctrl["finite_rel_err"] > limits["finite_rel_err"]


_EXCHANGE_LEFT_OUT = """
import json, sys, types
sys.path[:0] = [{root!r}, {src!r}]
import jax
from repro.core.runtimes import _halo
from bench.tests import test_correct as t

def local_only(local, r, num_devices, axis="shard", *, row_axis=0):
    n = local.shape[row_axis]
    take = jax.lax.slice_in_dim
    return (take(local, n - r, n, axis=row_axis),
            take(local, 0, r, axis=row_axis))

if {broken}:
    _halo.exchange_halos = local_only
res = t.run_cell("stencil_1d.ladder.x4", found=t.small_x4(64))
print(json.dumps({{"correct": res["correct"], "checks": res["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True],
                         ids=["exchange", "exchange_left_out"])
def test_exchange_left_out_is_not_correct(broken):
    """Four host devices, in a child process of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _EXCHANGE_LEFT_OUT.format(root=str(harness.ROOT),
                                     src=str(harness.ROOT / "src"),
                                     broken=broken)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken), res["checks"]
