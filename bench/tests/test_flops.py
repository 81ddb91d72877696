"""Task Bench's FLOP count."""
import pytest

from bench import flops


def test_task_flops_is_two_per_fma():
    assert flops.task_flops("compute_bound", 64, 16) == 2 * 64 * 16
    assert flops.task_flops("empty", 64, 16) == 0
    with pytest.raises(ValueError):
        flops.task_flops("memory_bound", 64, 16)
