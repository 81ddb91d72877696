"""Low-overhead span recorder for runtime telemetry.

The paper's contribution is *quantifying* where a runtime spends time;
this module is the in-process evidence source. A :class:`Tracer` records
nested :class:`Span` intervals with monotonic microsecond timestamps and
a category tag from the fixed taxonomy:

  dispatch           host work issuing device programs (per launch / step /
                     task — the quantity `serialized` maximizes)
  exchange           halo / stride transport walls (tagged with impl+depth)
  compute.boundary   the pipelined boundary phase (2*S*r edge rows)
  compute.interior   interior / whole-block kernel walls
  gather             full-state all-gather walls (the allgather plan)
  fault              fault handling: detection, retry/backoff sleeps, launch
                     replays, evictions (repro.resilience) — the recovery
                     tax, attributed like any other wall so a faulted run's
                     decomposition shows exactly where recovery spent time
  idle               wall not covered by any recorded span (derived by
                     decompose.py, but recordable explicitly too)

Two non-wall categories exist for structured records:

  build              set-up phases of a runtime's build (planning, host
                     operand tables, program construction, the first call
                     that compiles); recorded, never attributed as run wall
  decision           zero-length records (scheduler verdicts etc.); their
                     attrs are the payload, they carry no wall.

Tracing is OFF by default: runtimes hold the shared :data:`NULL_TRACER`,
whose ``span()`` returns one reusable no-op context (no allocation, no
timestamp) — the <1%-overhead contract tests/test_obs.py asserts.

Layer spans (:func:`layer_span`) mark the boundaries of the path the chip
runs. Each one is a ``jax.profiler.TraceAnnotation`` (so it lands on the
profiler's host plane, on the device trace's clock, whenever a profiler
session is active), a ``(calls, seconds)`` entry in the process-wide
counter table read by :func:`counters`, and a :class:`Span` when the
runtime's tracer is enabled.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Tuple, Union

from jax.profiler import TraceAnnotation

#: The attribution taxonomy (every microsecond of wall lands in one).
CATEGORIES = (
    "dispatch",
    "exchange",
    "compute.boundary",
    "compute.interior",
    "gather",
    "fault",
    "idle",
)

#: Wall category for fault detection/recovery work (repro.resilience).
CAT_FAULT = "fault"

#: Set-up phase of a build: recorded, never attributed as run wall.
CAT_BUILD = "build"
#: Zero-length structured record (scheduler decisions etc.).
CAT_DECISION = "decision"

_KNOWN = set(CATEGORIES) | {CAT_BUILD, CAT_DECISION}


@dataclasses.dataclass
class Span:
    """One recorded interval. Timestamps are microseconds on the
    ``time.perf_counter`` monotonic clock (comparable within a process,
    meaningless across processes)."""

    name: str
    category: str
    start_us: float
    end_us: float
    depth: int = 0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration_us(self) -> float:
        return self.end_us - self.start_us


class _SpanCtx:
    """Context manager for one enabled span (kept tiny: two clock reads
    plus one list append per span)."""

    __slots__ = ("_tr", "_name", "_category", "_attrs", "_start")

    def __init__(self, tr: "Tracer", name: str, category: str, attrs):
        self._tr = tr
        self._name = name
        self._category = category
        self._attrs = attrs

    def __enter__(self) -> "_SpanCtx":
        self._tr._depth += 1
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        tr = self._tr
        tr._depth -= 1
        tr.spans.append(Span(self._name, self._category,
                             self._start * 1e6, end * 1e6,
                             tr._depth, self._attrs))
        return False


class Tracer:
    """Records spans. One instance per traced runtime / run."""

    enabled = True

    def __init__(self) -> None:
        self.spans: List[Span] = []
        self._depth = 0

    @staticmethod
    def now_us() -> float:
        return time.perf_counter() * 1e6

    def span(self, name: str, category: str, **attrs) -> _SpanCtx:
        """Context manager recording [enter, exit] under ``category``."""
        if category not in _KNOWN:
            raise ValueError(
                f"unknown span category {category!r}; known: {sorted(_KNOWN)}")
        return _SpanCtx(self, name, category, attrs)

    def add(self, name: str, category: str, start_us: float, end_us: float,
            **attrs) -> None:
        """Record an interval with explicit timestamps (e.g. a probe wall
        measured around someone else's timing loop)."""
        if category not in _KNOWN:
            raise ValueError(
                f"unknown span category {category!r}; known: {sorted(_KNOWN)}")
        self.spans.append(Span(name, category, start_us, end_us,
                               self._depth, attrs))

    def instant(self, name: str, **attrs) -> None:
        """Zero-length decision record; ``attrs`` are the payload."""
        t = self.now_us()
        self.spans.append(Span(name, CAT_DECISION, t, t, self._depth, attrs))

    def clear(self) -> None:
        self.spans.clear()
        self._depth = 0


class _NullSpanCtx:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullSpanCtx()


class NullTracer:
    """The disabled fast path: every call is a no-op and ``span()`` returns
    ONE preallocated context — no allocation, no clock read. ``__slots__``
    is empty so the instance cannot even grow state by accident."""

    __slots__ = ()
    enabled = False
    spans: Tuple[Span, ...] = ()

    def span(self, name: str, category: str, **attrs) -> _NullSpanCtx:
        return _NULL_CTX

    def add(self, *a, **k) -> None:
        return None

    def instant(self, *a, **k) -> None:
        return None

    def clear(self) -> None:
        return None

    @staticmethod
    def now_us() -> float:
        return 0.0


#: The shared disabled tracer (runtimes default to this).
NULL_TRACER = NullTracer()

TracerLike = Union[Tracer, NullTracer]


def coerce_tracer(opt) -> TracerLike:
    """The ``trace=`` runtime option -> a tracer.

    None/False (default)  -> NULL_TRACER (provably near-zero cost)
    True / "on" / 1       -> a fresh Tracer
    a Tracer/NullTracer   -> itself (callers share one recorder)
    """
    if opt is None or opt is False:
        return NULL_TRACER
    if isinstance(opt, (Tracer, NullTracer)):
        return opt
    if opt is True or opt == 1 or (isinstance(opt, str)
                                   and opt.lower() in ("on", "true", "1")):
        return Tracer()
    raise ValueError(f"cannot interpret trace option {opt!r}: use "
                     f"True/False, 'on', or a Tracer instance")


# ----------------------------------------------------------- layer spans

#: name -> [calls, seconds], over every layer span of the process
_COUNTERS: Dict[str, List[float]] = {}
_COUNTERS_LOCK = threading.Lock()


class _LayerSpan:
    """One layer span: a profiler annotation, a counter entry and, when
    the tracer is enabled, a recorded Span (see :func:`layer_span`)."""

    __slots__ = ("_name", "_ann", "_span", "_t0")

    def __init__(self, tracer: TracerLike, name: str, category: str,
                 attrs: Dict[str, Any]):
        self._name = name
        self._ann = TraceAnnotation(name, **attrs)
        self._span = (tracer.span(name, category, **attrs)
                      if tracer.enabled else None)

    def set(self, **attrs) -> None:
        """Attach attrs known only once the span is open (e.g. the plan a
        build resolved) to the annotation and the recorded span."""
        self._ann.set_metadata(**attrs)
        if self._span is not None:
            self._span._attrs.update(attrs)

    def __enter__(self) -> "_LayerSpan":
        self._ann.__enter__()
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        if self._span is not None:
            self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        with _COUNTERS_LOCK:
            c = _COUNTERS.get(self._name)
            if c is None:
                _COUNTERS[self._name] = [1, dt]
            else:
                c[0] += 1
                c[1] += dt
        return False


def layer_span(tracer: TracerLike, name: str, *,
               category: str = CAT_BUILD, **attrs) -> _LayerSpan:
    """Context manager marking one layer boundary of the real path.

    Opens ``jax.profiler.TraceAnnotation(name)`` with ``attrs`` as its
    stats (a no-op without a profiler session), adds the span's wall to
    the process-wide ``(calls, seconds)`` counter under ``name`` (always),
    and records a ``category`` Span into ``tracer`` when it is enabled.
    """
    return _LayerSpan(tracer, name, category, attrs)


def counters() -> Dict[str, Tuple[int, float]]:
    """Snapshot of the layer-span counters: name -> (calls, seconds)."""
    with _COUNTERS_LOCK:
        return {k: (int(c), s) for k, (c, s) in _COUNTERS.items()}


def reset_counters() -> None:
    """Clear the layer-span counter table."""
    with _COUNTERS_LOCK:
        _COUNTERS.clear()
