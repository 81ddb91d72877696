"""operands_s: seconds pallas_step spent building host-side operand tables
(its ``pallas_step.operands`` spans) over set-up's rung builds, from the
program's counters. Moves setup_s."""
from bench import program_counters


def read(record):
    return program_counters.seconds("pallas_step.operands")
