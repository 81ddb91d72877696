"""The Task Bench kernels compile for a TPU v5e chip at real widths.

Nothing runs: each test lowers one kernel entry for a chip of a described
``v5e:2x2`` topology and asserts that Mosaic accepted it (the compiled
module holds a ``tpu_custom_call``). This guards the forms the chip's
compiler takes (DESIGN.md §4) with no chip attached. The topology is
described inside a fixture, never at import, so every test worker collects
the same tests and only the worker given this file loads the TPU library.
"""
import functools
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bodies import memory_bound_pallas
from repro.kernels.schedule import DEFAULT_GATHER_WIDTH_CAP
from repro.kernels.taskbench_compute import taskbench_compute_pallas
from repro.kernels.taskbench_step import (
    taskbench_step_boundary,
    taskbench_step_interior,
    taskbench_step_pallas,
)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `python -m pytest` adds cwd; be explicit
    sys.path.insert(0, str(ROOT))

from bench.trace_reduce import KERNEL  # noqa: E402

W = 4096  # the chip smoke's width
PAYLOAD = 64
BODY = dict(kind="compute_bound", iterations=64, scratch=2048,
            interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _window(K, M, depth_masks=0):
    """(src, idx, wgt[, act]) shapes of a radius-1 window launch."""
    shapes = [((K, M + (0 if depth_masks else 2), PAYLOAD), jnp.float32),
              ((K, 1, 1), jnp.int32), ((K, M, 3), jnp.float32)]
    if depth_masks:
        shapes.append(((K, depth_masks), jnp.float32))
    return shapes


def _onehot(K, width, depths, D=3):
    lead = (K, depths) if depths > 1 else (K,)
    shapes = [((K, width, PAYLOAD), jnp.float32),
              (lead + (width, D), jnp.int32), (lead + (width, D), jnp.float32)]
    if depths > 1:
        shapes.append(((K, depths), jnp.float32))
    return shapes


# name -> (callable, shapes); S>1 entries take the act mask positionally
CASES = {
    "window_s1": (functools.partial(taskbench_step_pallas, combine="window",
                                    **BODY),
                  _window(1, W)),
    "window_blocked_k4_s8": (
        lambda s, i, w, a: taskbench_step_pallas(
            s, i, w, a, combine="window", steps_per_launch=8, **BODY),
        _window(4, 1024 + 16, depth_masks=8)),
    # the widest blocked launch that fits VMEM (6144 + 16 rows does not)
    "window_blocked_widest": (
        lambda s, i, w, a: taskbench_step_pallas(
            s, i, w, a, combine="window", steps_per_launch=8, **BODY),
        _window(1, 5120 + 16, depth_masks=8)),
    "pair": (functools.partial(taskbench_step_pallas, combine="pair", **BODY),
             [((1, 2 * W, PAYLOAD), jnp.float32), ((1, 1, 1), jnp.int32),
              ((1, W, 1), jnp.float32)]),
    "interior_phase": (
        lambda s, i, w, a: taskbench_step_interior(
            s, i, w, a, depth=8, combine="window", steps_per_launch=8,
            **BODY),
        [((1, W, PAYLOAD), jnp.float32), ((1, 1, 1), jnp.int32),
         ((1, W, 3), jnp.float32), ((1, 8), jnp.float32)]),
    "boundary_phase": (
        lambda l, r, i, w, a: taskbench_step_boundary(
            l, r, i, w, a, depth=8, combine="window", steps_per_launch=8,
            **BODY),
        [((1, 24, PAYLOAD), jnp.float32), ((1, 24, PAYLOAD), jnp.float32),
         ((1, 1, 1), jnp.int32), ((1, 48, 3), jnp.float32),
         ((1, 8), jnp.float32)]),
    # the all-gather cap; 768 rows still fit VMEM, 896 do not
    "onehot_s1_cap": (
        functools.partial(taskbench_step_pallas, combine="onehot", **BODY),
        _onehot(1, DEFAULT_GATHER_WIDTH_CAP, 1)),
    "onehot_time_varying_s4_cap": (
        lambda s, i, w, a: taskbench_step_pallas(
            s, i, w, a, combine="onehot", steps_per_launch=4, **BODY),
        _onehot(1, DEFAULT_GATHER_WIDTH_CAP, 4)),
    "memory_bound_megakernel": (
        functools.partial(taskbench_step_pallas, combine="window",
                          block_rows=256, **dict(BODY, kind="memory_bound",
                                                 iterations=16)),
        _window(1, W)),
    "compute_bound_body": (
        functools.partial(taskbench_compute_pallas, iterations=64),
        [((W, PAYLOAD), jnp.float32)]),
    "memory_bound_body": (
        functools.partial(memory_bound_pallas, iterations=16, scratch=2048),
        [((W, PAYLOAD), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = CASES[name]
    text = _compile(fn, *(_sds(one_chip, s, d) for s, d in shapes))
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in module"


#: launch kind -> (its pallas_call's name, the CASES entry that compiles it)
LAUNCH_NAMES = {
    "s1": ("taskbench_step_s1", "window_s1"),
    "blocked": ("taskbench_step_blocked", "window_blocked_k4_s8"),
}


@pytest.mark.parametrize("launch", sorted(LAUNCH_NAMES))
def test_kernel_instruction_names_match_the_trace_reducer(
        launch, one_chip, no_compile_cache):
    """Each launch kind's custom call keeps its explicit name in the
    compiled module, and the benchmark's trace reduction finds every
    Task Bench custom call by that name."""
    name, case = LAUNCH_NAMES[launch]
    fn, shapes = CASES[case]
    text = _compile(fn, *(_sds(one_chip, s, d) for s, d in shapes))
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text)
    assert calls, f"{launch}: no Mosaic kernel in module"
    assert all(c.startswith(name) for c in calls), calls
    assert all(KERNEL.search(c) for c in calls), calls


def _computation(text: str, name: str) -> str:
    """The body of HLO computation ``name`` in a compiled module's text."""
    m = re.search(r"^%" + re.escape(name) + r" .*?^}", text, re.M | re.S)
    assert m, f"no computation {name}"
    return m.group(0)


def _with_callees(text: str, name: str) -> str:
    """Computation ``name`` and every computation it calls, branches of a
    conditional included."""
    seen, todo, out = set(), [name], []
    while todo:
        n = todo.pop()
        if n in seen:
            continue
        seen.add(n)
        body = _computation(text, n)
        out.append(body)
        for ref in re.findall(
                r"(?:calls=(%[\w.-]+)|branch_computations=\{([^}]*)\})",
                body):
            todo += re.findall(r"%([\w.-]+)", " ".join(ref))
    return "\n".join(out)


def test_s1_halo_loop_touches_the_state_only_in_the_megakernel(
        topo, no_compile_cache, monkeypatch):
    """The S=1 halo program at the benchmark cell's shape (W 4096,
    payload 64), lowered for one described v5e chip: the scanned loop
    runs two steps an iteration and holds exactly one Mosaic launch,
    ``taskbench_step_s1``, for each, and no concatenate, pad, slice or
    copy as long as the state's rows — the state stays in the
    megakernel's tiled, halo-extended layout, alternating between two
    buffers, and only the halo's edge rows move around it."""
    from repro.core import KernelSpec, TaskGraph, get_runtime
    from repro.kernels import ops

    real_jit = jax.jit
    compiled = []

    def lowering_jit(f, **kw):  # the built program, compiled, not run
        return lambda *args: compiled.append(
            real_jit(f, **kw).lower(*args).compile().as_text())

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "jit", lowering_jit)
    monkeypatch.setattr(jax, "device_put", lambda x, sharding=None: _sds(
        sharding, x.shape, x.dtype))
    g = TaskGraph(steps=9, width=W, payload=PAYLOAD, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 1))
    rt = get_runtime("pallas_step", devices=topo.devices[:1])
    rt._build_halo_stacked(g, 1)(jnp.zeros((W, PAYLOAD), jnp.float32))
    monkeypatch.undo()
    (text,) = compiled

    loops = re.findall(r"= \(.*\) while\(.*body=%([\w.-]+)", text)
    assert len(loops) == 1, loops
    body = _with_callees(text, loops[0])
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', body)
    assert len(calls) == 2, calls
    assert all(c.startswith("taskbench_step_s1") for c in calls), calls
    moves = [(op, dims) for dims, op in re.findall(
        r"= \w+\[([\d,]*)\]\S* (concatenate|pad|slice|copy)\(", body)
        if any(int(d) >= W for d in dims.split(",") if d)]
    assert not moves, moves


def test_fft_stride_loop_launches_only_the_pair_megakernel(
        topo, no_compile_cache, monkeypatch):
    """The fft program at the stencil cell's shape (W 4096, payload 64,
    1000 steps), built through ``pallas_step``'s default plan
    dispatch and lowered for one described v5e chip: the stride plan at
    S=1, whose scanned loop runs the 12-level period straight-line, 12
    ``taskbench_step_s1`` custom calls that the trace reducer's
    ``KERNEL`` matches, with no ``conditional`` and no copy of the state
    (a switch would return each level's result through HBM and copy it
    back every step); the module's other launches are t=0 and the 3
    remainder levels (999 = 83 x 12 + 3). The in-block swap and the
    [x | partner] stack keep their named scopes in the compiled module."""
    from repro.core import KernelSpec, TaskGraph, get_runtime
    from repro.kernels import ops

    real_jit = jax.jit
    compiled = []

    def lowering_jit(f, **kw):  # the built program, compiled, not run
        return lambda *args: compiled.append(
            real_jit(f, **kw).lower(*args).compile().as_text())

    g = TaskGraph(steps=1000, width=W, payload=PAYLOAD, pattern="fft",
                  kernel=KernelSpec("compute_bound", 1))
    rt = get_runtime("pallas_step", devices=topo.devices[:1])
    plan = rt._schedule_for_graph(g)
    assert (plan.kind, plan.steps_per_launch) == ("stride", 1)
    assert rt.dispatches_per_run(g) == 1000
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "jit", lowering_jit)
    monkeypatch.setattr(jax, "device_put", lambda x, sharding=None: _sds(
        sharding, x.shape, x.dtype))
    rt.build(g)(jnp.zeros((W, PAYLOAD), jnp.float32))
    monkeypatch.undo()
    (text,) = compiled

    assert " conditional(" not in text
    launches = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(launches) == 1 + 12 + 3, len(launches)
    loops = re.findall(r"= \(.*\) while\(.*body=%([\w.-]+)", text)
    assert len(loops) == 1, loops
    body = _with_callees(text, loops[0])
    calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', body)
    assert len(calls) == 12, calls
    assert all(c.startswith("taskbench_step_s1") for c in calls), calls
    assert all(KERNEL.search(c) for c in calls), calls
    copies = [dims for dims in re.findall(
        r"= \w+\[([\d,]*)\]\S* copy\(", body)
        if any(int(d) >= W for d in dims.split(",") if d)]
    assert not copies, copies
    for scope in ("xor_swap", "pair_src"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", body), scope
