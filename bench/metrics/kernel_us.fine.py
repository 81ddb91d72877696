"""kernel_us.fine: device time of the megakernel's launches per graph step
at the finest rung, averaged over the chips. Moves metg_ns."""
from bench import trace_reduce


def read(record):
    ns = trace_reduce.per_graph_step(record, 0, trace_reduce.kernel_ns)
    return ns / 1e3 if ns else None
