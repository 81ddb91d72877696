"""Per-category wall attribution from span evidence.

Attribution contract
--------------------
``category_walls`` unions each category's span intervals (nested or
overlapping spans of one category never double-count). ``idle`` is
derived: the run's extent minus the union of ALL attributed intervals.
Non-wall records — ``decision`` instants and ``build`` set-up phases —
are never attributed and never widen the extent, so a traced run's
summary covers the run alone. Per-op device time comes from the
profiler's device trace, not from these host spans (DESIGN.md §10).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

from repro.obs.tracer import CAT_BUILD, CAT_DECISION, CATEGORIES, Span

#: decomposition summary schema (rides inside benchmark rows/artifacts)
DECOMPOSE_SCHEMA_VERSION = 1

#: categories that carry no run wall
_NO_WALL = (CAT_BUILD, CAT_DECISION)


def merged_intervals(
    intervals: Iterable[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    """Sorted, overlap-merged copy of ``intervals``."""
    ivs = sorted((a, b) for a, b in intervals if b > a)
    out: List[Tuple[float, float]] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_us(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merged_intervals(intervals))


def category_walls(spans: Sequence[Span]) -> Dict[str, float]:
    """Per-category attributed wall (us): interval unions per category;
    ``idle`` is the run extent minus everything attributed."""
    walls = {c: 0.0 for c in CATEGORIES}
    by_cat: Dict[str, List[Tuple[float, float]]] = {}
    for s in spans:
        if s.category not in _NO_WALL:
            by_cat.setdefault(s.category, []).append((s.start_us, s.end_us))
    for cat, ivs in by_cat.items():
        walls[cat] = walls.get(cat, 0.0) + union_us(ivs)
    attributed = union_us(iv for ivs in by_cat.values() for iv in ivs)
    walls["idle"] = walls.get("idle", 0.0) + max(
        wall_extent_us(spans) - attributed, 0.0)
    return walls


def wall_extent_us(spans: Sequence[Span]) -> float:
    """Run extent: earliest start to latest end over wall spans."""
    real = [s for s in spans if s.category not in _NO_WALL]
    if not real:
        return 0.0
    return max(s.end_us for s in real) - min(s.start_us for s in real)


def decision_records(spans: Sequence[Span]) -> List[Dict]:
    return [dict(s.attrs, name=s.name) for s in spans
            if s.category == CAT_DECISION]


def summarize(spans: Sequence[Span]) -> Dict:
    """JSON-safe decomposition of one traced run (what benchmark rows
    carry across the worker subprocess boundary)."""
    walls = category_walls(spans)
    extent = wall_extent_us(spans)
    total = sum(walls.values())
    fractions = {c: (w / total if total > 0 else 0.0)
                 for c, w in walls.items()}
    return {
        "schema": DECOMPOSE_SCHEMA_VERSION,
        "span_count": len(spans),
        "wall_us": extent,
        "categories_us": walls,
        "fractions": fractions,
        "decisions": decision_records(spans),
    }
