"""The readers of the program's counters, on a counter table set by hand
and on a program that keeps no such counter."""
import importlib.util
from pathlib import Path

import pytest

import repro.obs

METRICS = Path(__file__).resolve().parents[1] / "metrics"

READERS = {"operands_s": "pallas_step.operands",
           "first_call_s": "pallas_step.first_call"}


def _reader(metric):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric}", METRICS / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reads_the_counter_seconds(metric, monkeypatch):
    table = {READERS[metric]: (5, 1.25), "pallas_step.call": (40, 0.5)}
    monkeypatch.setattr(repro.obs, "counters", lambda: dict(table))
    assert _reader(metric).read({}) == 1.25


@pytest.mark.parametrize("metric", sorted(READERS))
def test_absent_counter_reads_none(metric, monkeypatch):
    monkeypatch.setattr(repro.obs, "counters", lambda: {})
    assert _reader(metric).read({}) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_program_without_counters_reads_none(metric, monkeypatch):
    monkeypatch.delattr(repro.obs, "counters")
    assert _reader(metric).read({}) is None
