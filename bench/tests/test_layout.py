"""Every entry of BENCHMARK.json resolves to its files, and the file keeps
to the benchmark's contract on names, units and bounds."""
import json
import re

import pytest

from bench import reference
from bench import run as harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves(cell):
    found = harness.resolve(SPEC, cell)
    assert found["driver"].is_file()
    assert found["config"]["name"] == found["cell"]["config"]
    assert found["config"]["dtype"] in reference.LOWER
    assert set(found["limits"]) >= {"class_mismatch", "finite_rel_err"}
    reference.load_pattern(found["config"]["pattern"])
    harness.load_module(found["driver"], "driver_under_test")
    names = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert found["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_resolves(metric):
    mod = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py",
                              f"metric_{metric}")
    assert callable(mod.read)


def test_names_units_and_bounds():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_paths_hold_only_the_benchmark():
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
