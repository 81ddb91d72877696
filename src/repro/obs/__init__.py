"""Structured runtime telemetry (DESIGN.md §10).

tracer.py     span recorder (categories, monotonic us timestamps, the
              off-by-default NULL_TRACER fast path) and the layer spans:
              profiler annotations plus the process-wide counter table
export.py     Chrome trace_event JSON + JSONL dumps
decompose.py  per-category wall attribution
"""
from repro.obs.decompose import (
    DECOMPOSE_SCHEMA_VERSION,
    category_walls,
    decision_records,
    summarize,
    union_us,
    wall_extent_us,
)
from repro.obs.export import (
    TRACE_SCHEMA_VERSION,
    span_dicts,
    to_chrome_trace,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.tracer import (
    CAT_BUILD,
    CAT_DECISION,
    CAT_FAULT,
    CATEGORIES,
    NULL_TRACER,
    NullTracer,
    Span,
    Tracer,
    coerce_tracer,
    counters,
    layer_span,
    reset_counters,
)

__all__ = [
    "CATEGORIES", "CAT_BUILD", "CAT_DECISION", "CAT_FAULT",
    "NULL_TRACER", "NullTracer",
    "Span", "Tracer", "coerce_tracer",
    "counters", "layer_span", "reset_counters",
    "TRACE_SCHEMA_VERSION", "span_dicts", "to_chrome_trace",
    "write_chrome_trace", "write_jsonl",
    "DECOMPOSE_SCHEMA_VERSION", "category_walls", "decision_records",
    "summarize", "union_us", "wall_extent_us",
]
