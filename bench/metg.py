"""METG(50%): the smallest task granularity that keeps half of peak FLOP/s.

Task Bench's headline metric (Slaughter et al., SC'20; arXiv:2207.12127
§6.1), as this benchmark reads it from one run of a grain ladder:

  1. each rung runs whole graphs of one grain back to back for its share
     of the window; its rate is all useful FLOPs of the graphs it completed
     over all of its time;
  2. peak = the highest rung rate of the same run;
  3. efficiency(rung) = rate / peak;
  4. granularity(rung) = seconds per graph x chips / tasks per graph;
  5. METG = the granularity where the efficiency curve, taken in ascending
     granularity, first crosses the threshold from below, interpolated in
     log granularity between the two bracketing rungs. If the finest rung
     already meets the threshold, METG is that rung's granularity (an upper
     bound); if no rung does, there is no METG.

The arithmetic is kept with the benchmark so no change to the program can
move it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

THRESHOLD = 0.5


@dataclasses.dataclass(frozen=True)
class Rung:
    """One rung of a ladder run: ``graphs`` whole graphs in ``seconds``."""

    grain: int
    graphs: int
    seconds: float
    flops_per_graph: float
    tasks_per_graph: int
    chips: int

    @property
    def flops_per_second(self) -> float:
        return self.graphs * self.flops_per_graph / self.seconds

    @property
    def granularity_s(self) -> float:
        """Seconds of device time per task: wall x chips / tasks."""
        return self.seconds / self.graphs * self.chips / self.tasks_per_graph


def efficiency_curve(rungs: Sequence[Rung]) -> List[tuple]:
    """(granularity_s, efficiency) per rung, ascending granularity."""
    if not rungs:
        return []
    peak = max(r.flops_per_second for r in rungs)
    return sorted((r.granularity_s, r.flops_per_second / peak) for r in rungs)


def metg_seconds(rungs: Sequence[Rung],
                 threshold: float = THRESHOLD) -> Optional[float]:
    """METG in seconds per task, or None when no rung meets the threshold."""
    curve = efficiency_curve(rungs)
    if not curve:
        return None
    if curve[0][1] >= threshold:
        return curve[0][0]
    for (g0, e0), (g1, e1) in zip(curve, curve[1:]):
        if e0 < threshold <= e1:
            frac = (threshold - e0) / max(e1 - e0, 1e-12)
            return math.exp(math.log(g0) + frac * (math.log(g1) - math.log(g0)))
    return None
