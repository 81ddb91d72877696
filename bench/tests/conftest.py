"""Tests of the benchmark itself. They run on the CPU (Pallas in interpret
mode) at small sizes:  JAX_PLATFORMS=cpu python -m pytest -q bench/tests"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
