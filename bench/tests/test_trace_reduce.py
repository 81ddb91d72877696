"""bench/trace_reduce.py against hand-made intervals and against small
traces recorded on the chip (bench/tests/record_trace.py): a stencil_1d
graph of STEPS steps at grain 1 through pallas_step on v5e chips,
``data/stencil_1d_x<chips>.xplane.pb``; the one-chip trace is there."""
import re
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as T
from bench.tests.record_trace import STEPS

FIXTURES = sorted((Path(__file__).resolve().parent / "data").glob(
    "stencil_1d_x*.xplane.pb"))


def op(name, start, end, hlo=""):
    return T.Op(name, float(start), float(end), hlo or f"%{name} = f32[1] op()")


def test_containers_are_dropped():
    ops = [op("while.2", 0, 100), op("a.1", 1, 10), op("b", 12, 30),
           op("cond.1", 40, 90), op("c", 41, 50), op("d", 95, 99)]
    assert [o.name for o in T.leaves(ops)] == ["a.1", "b", "c", "d"]


def test_busy_and_gaps():
    ops = [op("a", 0, 10), op("b", 5, 20), op("c", 30, 35), op("d", 50, 60)]
    assert T.busy_ns(ops) == 35
    assert T.idle_gaps(ops) == [(35, 50), (20, 30)]


def test_exposed_collective_is_what_nothing_else_covers():
    ops = [op("collective-permute-start", 0, 10), op("fusion.1", 5, 15),
           op("collective-permute-done.1", 20, 40), op("pad", 30, 35),
           op("all-gather.2", 50, 52)]
    # 0-5, 20-30, 35-40 and 50-52 have a collective and nothing else
    assert T.exposed_collective_ns(ops) == 5 + 10 + 5 + 2


def test_kernel_is_a_custom_call_by_name():
    call = "%taskbench_step_pallas.3 = f32[1,8,128] custom-call(f32[1,8,128] %x)"
    slice_ = ("%slice_bitcast_fusion.3 = f32[8,64] fusion(f32[1,8,128] "
              "%taskbench_step_pallas.3)")
    assert T.Op("taskbench_step_pallas.3", 0, 1, call).is_kernel()
    assert not T.Op("slice_bitcast_fusion.3", 0, 1, slice_).is_kernel()
    assert T.Op("taskbench_step_pallas.3", 0, 1, call).kind == \
        "taskbench_step_pallas"


def _timeline(ops, lo, hi):
    """Covered nanoseconds as a boolean mask: the plain way to count."""
    mask = np.zeros(int(np.ceil(hi - lo)) + 1, bool)
    for o in ops:
        mask[int(round(o.start_ns - lo)):int(round(o.end_ns - lo))] = True
    return mask


@pytest.fixture(scope="module", params=FIXTURES, ids=lambda p: p.stem)
def recorded(request):
    chips = int(re.search(r"_x(\d+)\.", request.param.name).group(1))
    return chips, T.load(request.param)


#: read once off each recorded trace, per chip: busy ns, span from the first
#: op to the last, megakernel ns, exposed collective ns (the one-chip ring
#: permutes to itself)
KNOWN = {
    "stencil_1d_x1": {0: (44267.0, 44378.0, 16649.0, 110.0)},
}


@pytest.mark.parametrize("stem", sorted(KNOWN))
def test_recorded_known_answers(stem):
    trace = T.load(Path(__file__).resolve().parent / "data" / f"{stem}.xplane.pb")
    got = {d: (T.busy_ns(ops), ops[-1].end_ns - ops[0].start_ns,
               T.kernel_ns(ops), T.exposed_collective_ns(ops))
           for d, ops in trace.devices.items()}
    assert got == KNOWN[stem]


#: each per-layer reader on a record whose rungs are one graph of the
#: one-chip trace: (16649 ns of megakernel) / 6 steps, and so on
READS = {
    "kernel_us.fine": 16649.0 / STEPS / 1e3,
    "kernel_us.coarse": 16649.0 / STEPS / 1e3,
    "launches_per_step": 1.0,
    # no two ops overlap on this trace: busy minus the megakernel
    "step_ops_us.fine": (44267.0 - 16649.0) / STEPS / 1e3,
    "idle_share": 100.0 * (1 - 44267.0 / 44378.0),
}


@pytest.mark.parametrize("metric", sorted(READS))
def test_reader_on_recorded_trace(metric):
    from bench import run as harness

    trace = T.load(FIXTURES[0])
    rung = dict(graphs=1, seconds=1.0, trace=trace)
    record = dict(steps=STEPS, rungs=[dict(rung, grain=1),
                                      dict(rung, grain=256)])
    empty = dict(steps=STEPS, rungs=[dict(rung, grain=1, trace=None)] * 2)
    mod = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py",
                              f"metric_{metric}")
    assert mod.read(record) == pytest.approx(READS[metric], rel=1e-9)
    assert mod.read(empty) is None


def test_fixtures_are_there():
    assert [p.name for p in FIXTURES] == ["stencil_1d_x1.xplane.pb"]


def test_recorded_trace_has_its_chips_and_one_launch_per_step(recorded):
    chips, trace = recorded
    assert sorted(trace.devices) == list(range(chips))
    for ops in trace.devices.values():
        assert len(T.kernel_ops(ops)) == STEPS


def test_recorded_busy_and_exposed_match_a_timeline(recorded):
    _, trace = recorded
    for ops in trace.devices.values():
        lo, hi = ops[0].start_ns, ops[-1].end_ns
        busy = _timeline(ops, lo, hi).sum()
        coll = _timeline([o for o in ops if o.is_collective()], lo, hi)
        rest = _timeline([o for o in ops if not o.is_collective()], lo, hi)
        assert T.busy_ns(ops) == pytest.approx(busy, abs=len(ops))
        exposed = (coll & ~rest).sum()
        assert exposed > 0
        assert T.exposed_collective_ns(ops) == pytest.approx(
            exposed, abs=len(ops))
        assert 0 < T.busy_ns(ops) <= hi - lo
