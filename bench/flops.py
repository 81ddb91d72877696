"""Useful FLOPs of Task Bench work, by Task Bench's own count.

The compute_bound body runs ``iterations`` fused multiply-adds on each of a
task's ``payload`` floats, so a task is ``2 * payload * iterations`` useful
FLOPs (Slaughter et al., SC'20), and an ``empty`` task none. Padding the
payload to the chip's 128 lanes adds no useful FLOPs.
"""


def task_flops(kind: str, payload: int, iterations: int) -> int:
    if kind == "compute_bound":
        return 2 * payload * iterations
    if kind == "empty":
        return 0
    raise ValueError(f"no FLOP count for kernel kind {kind!r}")
