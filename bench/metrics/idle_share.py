"""idle_share: the share of the finest rung's traced span, from its first
device operation to its last, in which no operation ran on the device, in
percent, averaged over the chips. Moves metg_ns."""
from bench import trace_reduce


def read(record):
    tr = record["rungs"][0]["trace"]
    if tr is None or not any(tr.devices.values()):
        return None
    shares = []
    for ops in tr.devices.values():
        span = ops[-1].end_ns - ops[0].start_ns
        shares.append(1.0 - trace_reduce.busy_ns(ops) / span)
    return 100.0 * sum(shares) / len(shares)
