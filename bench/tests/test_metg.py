"""Known answers for the METG arithmetic (bench/metg.py)."""
import math

import pytest

from bench.metg import Rung, metg_seconds


def _rung(grain, seconds_per_graph, flops_per_graph, graphs=10):
    return Rung(grain=grain, graphs=graphs, seconds=seconds_per_graph * graphs,
                flops_per_graph=flops_per_graph, tasks_per_graph=1000,
                chips=1)


def test_crossing_is_log_interpolated():
    # rates 25, 75, 100 FLOP/s at granularities 1, 2, 4 ms per task:
    # efficiency 0.25 -> 0.75 between 1 and 2 ms, so 0.5 lies halfway in
    # log granularity: sqrt(1 * 2) ms
    rungs = [_rung(1, 1.0, 25.0), _rung(2, 2.0, 150.0), _rung(4, 4.0, 400.0)]
    assert metg_seconds(rungs) == pytest.approx(math.sqrt(2) * 1e-3)


def test_finest_rung_already_efficient_gives_its_granularity():
    rungs = [_rung(1, 1.0, 60.0), _rung(2, 2.0, 200.0)]
    assert metg_seconds(rungs) == pytest.approx(1e-3)


def test_first_crossing_counts():
    # rates 10, 50, 5 FLOP/s: efficiency 0.2, 1.0, 0.1 in ascending
    # granularity; the first crossing lies between 1 and 2 ms, at
    # frac (0.5 - 0.2) / 0.8 of the way in log granularity
    rungs = [_rung(1, 1.0, 10.0), _rung(2, 2.0, 100.0), _rung(4, 4.0, 20.0)]
    assert metg_seconds(rungs) == pytest.approx(2 ** (0.3 / 0.8) * 1e-3)
    assert metg_seconds([]) is None


def test_granularity_counts_chips():
    r = Rung(grain=1, graphs=4, seconds=2.0, flops_per_graph=1.0,
             tasks_per_graph=1000, chips=4)
    assert r.granularity_s == pytest.approx(0.5 * 4 / 1000)
    assert r.flops_per_second == pytest.approx(2.0)
