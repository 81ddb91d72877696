"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single-CPU device set (the 512-device forcing belongs ONLY to
launch/dryrun.py). Tests that need multi-device meshes spawn subprocesses
(see test_distributed.py) or use what `jax.devices()` offers.
"""
import os

import pytest

# determinism + quieter logs
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Scheduling tests assert the ANALYTIC cost model's verdicts; a developer's
# ambient calibration cache (artifacts/bench/cost_model.json) would silently
# flip them. "off" pins the analytic fallback; cost-model tests that need a
# cache point REPRO_COST_MODEL at a tmp_path file via monkeypatch.
os.environ.setdefault("REPRO_COST_MODEL", "off")


@pytest.fixture(scope="session")
def rng_key():
    import jax

    return jax.random.PRNGKey(0)
