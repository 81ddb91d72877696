"""first_call_s: seconds of the built programs' first calls (trace, lower,
compile or cache read, enqueue; the ``pallas_step.first_call`` spans) over
set-up, from the program's counters. Moves setup_s."""
from bench import program_counters


def read(record):
    return program_counters.seconds("pallas_step.first_call")
