"""step_ops_us.fine: device time per graph step, at the finest rung, of the
operations the launch loop runs around the megakernel (padding, slicing,
halo concatenation and permutes), averaged over the chips. Moves
metg_ns."""
from bench import trace_reduce


def read(record):
    ns = trace_reduce.per_graph_step(
        record, 0, lambda ops: sum(o.dur_ns for o in ops if not o.is_kernel()))
    return None if ns is None else ns / 1e3
