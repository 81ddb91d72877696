"""Read the program's own layer-span counters (``repro.obs.counters``).

The counters are process-wide ``(calls, seconds)`` per span name, taken
whether or not a profiler session runs. A benchmark run builds and first
calls every program during set-up, before any profiler session, so a
reader that runs after the window reads set-up's totals.
"""
from typing import Optional


def seconds(name: str) -> Optional[float]:
    """Seconds under the counter ``name``; None where the program keeps no
    such counter."""
    try:
        from repro.obs import counters
    except ImportError:
        return None
    entry = counters().get(name)
    return None if entry is None else entry[1]
