"""kernel_us.coarse: device time of the megakernel's launches per graph
step at the top (coarsest) rung, averaged over the chips. Moves
gflops_coarse."""
from bench import trace_reduce


def read(record):
    ns = trace_reduce.per_graph_step(record, -1, trace_reduce.kernel_ns)
    return ns / 1e3 if ns else None
