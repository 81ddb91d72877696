"""launches_per_step: megakernel launches in the device trace per graph
step, at the finest rung, averaged over the chips. Moves metg_ns."""
from bench import trace_reduce


def read(record):
    n = trace_reduce.per_graph_step(
        record, 0, lambda ops: len(trace_reduce.kernel_ops(ops)))
    return n or None
