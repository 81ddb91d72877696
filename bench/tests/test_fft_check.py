"""``correct`` in ``fft.ladder`` sees the faults a butterfly's output can
hold: each planted one reads false, the unbroken program true.

A stride table that leaves one level out of every period stops each
non-finite value's light cone at half of its column's rows; a partner
taken along the payload axis instead of the rows carries non-finite
values into columns where the reference has none. The run is
``test_correct.py``'s small one on the CPU; that file plants its own three
faults in every cell.
"""
import pytest

from bench.tests import test_correct as tc

CELL = "fft.ladder"


def _level_left_out(monkeypatch):
    """The highest level's slot runs the lowest level's stride."""
    from repro.core import patterns

    strides = patterns.butterfly_slot_strides

    def short(g):
        s = strides(g)
        return s if g.pattern != "fft" else s[:-1] + (s[0],)

    monkeypatch.setattr(patterns, "butterfly_slot_strides", short)


def _payload_swap(monkeypatch):
    """The in-block partner is the row's own payload, columns permuted by
    c -> c XOR (stride mod payload), where the rows should be."""
    import jax.numpy as jnp

    from repro.core.runtimes import pallas_step

    swap = pallas_step._xor_swap

    def along_payload(x, stride):
        k = stride % x.shape[1]
        if not k:
            return x
        return jnp.swapaxes(swap(jnp.swapaxes(x, 0, 1), k), 0, 1)

    monkeypatch.setattr(pallas_step, "_xor_swap", along_payload)


def test_unbroken_program_is_correct():
    res = tc.run_cell(CELL)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("plant", [_level_left_out, _payload_swap],
                         ids=["level_left_out", "payload_swap"])
def test_butterfly_fault_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = tc.run_cell(CELL)
    assert not res["correct"], res["checks"]
    assert res["checks"]["class_mismatch"]["value"] > 0
    assert res["failed"] > 0
