"""Reduce a JAX profiler trace (``.xplane.pb``) to device intervals.

    python bench/trace_reduce.py --dump <file.xplane.pb>   # look at a trace

``load`` reads the trace with ``jax.profiler.ProfileData`` and keeps, for
each TPU device, the leaf events of its op line (``XLA Ops``): one interval
per device operation, named by the operation's HLO text. Host events (the
Python threads and the runtime's) are kept apart. The functions below turn
those into the numbers the per-layer metrics read: busy time as the union
of op intervals, megakernel and collective events by name, and the part of
the collectives during which no other operation runs on that device.
"""
from __future__ import annotations

import argparse
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

#: the Task Bench megakernel's launches, by their HLO instruction name
KERNEL = re.compile(r"taskbench_step|_step_kernel|_blocked_step_kernel")
#: cross-chip collectives, synchronous or split into start/done halves
COLLECTIVE = re.compile(
    r"all-gather|collective-permute|all-reduce|all-to-all|reduce-scatter"
    r"|send|recv")
#: the device's op line; its control-flow ops (a while loop, a conditional)
#: span the ops they run and are dropped, so only leaf ops count as busy
_OP_LINE = "XLA Ops"


class Op(NamedTuple):
    name: str      # HLO instruction name, e.g. "taskbench_step_pallas.7"
    start_ns: float
    end_ns: float
    hlo: str       # the instruction's HLO text, as the trace names the event

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    @property
    def kind(self) -> str:
        """The instruction name without its numeric suffix."""
        return re.sub(r"(\.\d+)+$", "", self.name)

    def is_kernel(self) -> bool:
        return "custom-call(" in self.hlo and bool(KERNEL.search(self.name))

    def is_collective(self) -> bool:
        return bool(COLLECTIVE.search(self.name))


class Trace(NamedTuple):
    devices: Dict[int, List[Op]]  # device id -> leaf ops sorted by start
    host: List[Op]                # host events; ``hlo`` holds the thread


def _device_id(plane_name: str):
    m = re.fullmatch(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def _short_name(text: str) -> str:
    return text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(ops: Sequence[Op]) -> List[Op]:
    """Ops sorted by start, without those that contain the next op."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.end_ns))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= o.end_ns
            or nxt.end_ns > o.end_ns]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[int, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            ops = [Op(_short_name(e.name), e.start_ns,
                      e.start_ns + e.duration_ns, e.name)
                   for line in plane.lines if line.name == _OP_LINE
                   for e in line.events]
            devices[dev] = leaves(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append(Op(e.name, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       line.name))
    return Trace(devices, sorted(host, key=lambda o: o.start_ns))


# ------------------------------------------------------------- intervals


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals covering the same time."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Time covered by both of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(ops: Sequence[Op]) -> float:
    """Nanoseconds in which at least one operation ran."""
    return length(union((o.start_ns, o.end_ns) for o in ops))


def kernel_ops(ops: Sequence[Op]) -> List[Op]:
    return [o for o in ops if o.is_kernel()]


def kernel_ns(ops: Sequence[Op]) -> float:
    return sum(o.dur_ns for o in kernel_ops(ops))


def per_graph_step(record: dict, rung: int, per_chip) -> Optional[float]:
    """``per_chip(ops)`` averaged over the chips of one traced rung of a
    run's record, per graph step it ran; None where it has no trace."""
    r = record["rungs"][rung]
    tr = r["trace"]
    if tr is None or not tr.devices:
        return None
    vals = [per_chip(ops) for ops in tr.devices.values()]
    return sum(vals) / len(vals) / (r["graphs"] * record["steps"])


def exposed_collective_ns(ops: Sequence[Op]) -> float:
    """Collective time during which no other operation runs on the device."""
    coll = [o for o in ops if o.is_collective()]
    rest = [o for o in ops if not o.is_collective()]
    c = union((o.start_ns, o.end_ns) for o in coll)
    r = union((o.start_ns, o.end_ns) for o in rest)
    return length(c) - overlap(c, r)


def idle_gaps(ops: Sequence[Op]) -> List[Tuple[float, float]]:
    """Intervals between the device's busy spans, longest first."""
    spans = union((o.start_ns, o.end_ns) for o in ops)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(spans, spans[1:])]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(host: Sequence[Op], start: float, end: float) -> str:
    """The innermost host event that covers the middle of [start, end]."""
    mid = (start + end) / 2
    best = None
    for o in host:
        if o.start_ns <= mid <= o.end_ns and (best is None
                                               or o.dur_ns < best.dur_ns):
            best = o
    return best.name if best is not None else "no host event"


# ------------------------------------------------------------------ dump


def dump(path: str, events: int = 4) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:events]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns}"
                      f" stats={dict(e.stats)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dump", required=True, help="an .xplane.pb file")
    ap.add_argument("--events", type=int, default=4)
    args = ap.parse_args(argv)
    dump(args.dump, args.events)
    return 0


if __name__ == "__main__":
    sys.exit(main())
