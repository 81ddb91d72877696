"""The span tracer, layer spans, exporters, and wall decomposition
(DESIGN.md §10).

Four layers of coverage:

  * pure-unit: Span/Tracer semantics (nesting depth, category validation,
    the NullTracer fast path), exporter schemas, and the decompose interval
    math on SYNTHETIC spans with known answers;
  * parity: for every backend, the traced executor built by
    ``_build_traced`` must be numerically identical to the production
    ``execute`` path — tracing is evidence, never a different program
    (single-device in-process; the 2-device matrix runs in a subprocess);
  * layer spans: pallas_step's build and calls land in the profiler's host
    plane in order and in the process-wide counter table, on every plan;
  * the off-by-default contract: a runtime with tracing off holds the
    NULL_TRACER and opens no recording span while it measures.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.obs import (
    CAT_BUILD,
    CAT_DECISION,
    CATEGORIES,
    NULL_TRACER,
    NullTracer,
    Span,
    Tracer,
    coerce_tracer,
    counters,
    reset_counters,
    summarize,
    to_chrome_trace,
    union_us,
    write_chrome_trace,
    write_jsonl,
)
from repro.obs.decompose import category_walls, wall_extent_us

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- tracer --

def test_span_nesting_records_depth():
    tr = Tracer()
    with tr.span("outer", "dispatch"):
        with tr.span("inner", "compute.interior", step=3):
            pass
    # inner exits (and appends) first
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    inner, outer = tr.spans
    assert inner.depth == 1 and outer.depth == 0
    assert inner.attrs == {"step": 3}
    assert inner.start_us >= outer.start_us
    assert inner.end_us <= outer.end_us
    assert outer.duration_us >= inner.duration_us >= 0.0


def test_unknown_category_rejected():
    tr = Tracer()
    with pytest.raises(ValueError, match="unknown span category"):
        tr.span("x", "comms")
    with pytest.raises(ValueError, match="unknown span category"):
        tr.add("x", "comms", 0.0, 1.0)
    # every taxonomy member and both structured categories are accepted
    for cat in CATEGORIES + (CAT_BUILD,):
        with tr.span("x", cat):
            pass


def test_add_and_instant_and_clear():
    tr = Tracer()
    tr.add("probe", "exchange", 10.0, 25.0, probe=True, phase="exchange",
           per_launch_us=5.0)
    tr.instant("schedule.resolve", plan="halo")
    assert tr.spans[0].duration_us == 15.0
    dec = tr.spans[1]
    assert dec.category == CAT_DECISION
    assert dec.start_us == dec.end_us
    assert dec.attrs["plan"] == "halo"
    tr.clear()
    assert tr.spans == [] and tr._depth == 0


def test_coerce_tracer():
    assert coerce_tracer(None) is NULL_TRACER
    assert coerce_tracer(False) is NULL_TRACER
    assert isinstance(coerce_tracer(True), Tracer)
    assert isinstance(coerce_tracer("on"), Tracer)
    assert isinstance(coerce_tracer(1), Tracer)
    tr = Tracer()
    assert coerce_tracer(tr) is tr  # callers can share one recorder
    assert coerce_tracer(NULL_TRACER) is NULL_TRACER
    with pytest.raises(ValueError, match="trace option"):
        coerce_tracer("loud")


def test_null_tracer_is_inert():
    nt = NULL_TRACER
    assert isinstance(nt, NullTracer) and nt.enabled is False
    ctx1 = nt.span("a", "dispatch")
    ctx2 = nt.span("b", "nonsense-category")  # not even validated
    assert ctx1 is ctx2  # ONE preallocated context, no allocation
    with ctx1:
        pass
    nt.add("x", "exchange", 0.0, 1.0)
    nt.instant("x")
    nt.clear()
    assert nt.spans == ()


def test_null_tracer_records_nothing_over_a_measure(monkeypatch):
    """The off-by-default contract: a runtime built with tracing off holds
    NULL_TRACER, and a whole ``measure`` of a ``bsp`` graph never opens a
    recording span (``Tracer.span`` raises if it does)."""
    from repro.core import KernelSpec, TaskGraph, get_runtime

    def recording_span(self, *a, **k):
        raise AssertionError("a recording span was opened with tracing off")

    monkeypatch.setattr(Tracer, "span", recording_span)
    g = TaskGraph(steps=8, width=64, pattern="stencil_1d", payload=64,
                  kernel=KernelSpec("compute_bound", 64), radius=1, seed=0)
    rt = get_runtime("bsp")
    assert rt.tracer is NULL_TRACER
    sample, _ = rt.measure(g, reps=2, warmup=1)
    assert sample.wall_time > 0
    assert rt.tracer.spans == ()


# ------------------------------------------------------------- exporters --

def _spans_for_export():
    return [
        Span("launch", "dispatch", 10.0, 30.0, 0, {"launch": 0}),
        Span("decide", CAT_DECISION, 12.0, 12.0, 1, {"plan": "halo"}),
        Span("kernel", "compute.interior", 15.0, 28.0, 1, {}),
    ]


def test_chrome_trace_schema():
    doc = to_chrome_trace(_spans_for_export(), process_name="t")
    assert doc["schemaVersion"] == 1
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "t"
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(complete) == 2 and len(instants) == 1
    k = next(e for e in complete if e["name"] == "kernel")
    assert k["ts"] == 15.0 and k["dur"] == 13.0 and k["tid"] == 1
    assert k["args"]["category"] == "compute.interior"
    assert instants[0]["args"]["plan"] == "halo"


def test_write_chrome_trace_and_jsonl_roundtrip(tmp_path):
    spans = _spans_for_export()
    cpath = write_chrome_trace(str(tmp_path / "t.json"), spans)
    with open(cpath) as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) == 4  # metadata + 3 spans
    jpath = write_jsonl(str(tmp_path / "t.jsonl"), spans)
    lines = [json.loads(ln) for ln in open(jpath)]
    assert lines[0] == {"schema": 1}
    assert len(lines) == 4
    assert lines[1]["name"] == "launch" and lines[1]["end_us"] == 30.0
    assert lines[2]["attrs"] == {"plan": "halo"}


# ------------------------------------------------------------- decompose --

def test_union_merges_overlaps():
    assert union_us([(0, 10), (5, 15), (20, 25)]) == 20.0
    assert union_us([(0, 0), (3, 2)]) == 0.0  # degenerate dropped


def test_category_walls_no_double_count_and_idle():
    spans = [
        Span("a", "dispatch", 0.0, 10.0),
        Span("b", "dispatch", 5.0, 12.0),     # overlaps a: union, not sum
        Span("c", "exchange", 20.0, 30.0),
        Span("d", CAT_DECISION, 1.0, 1.0),    # never attributed
        Span("e", CAT_BUILD, -50.0, 0.0),     # set-up: no wall, no extent
    ]
    walls = category_walls(spans)
    assert walls["dispatch"] == 12.0
    assert walls["exchange"] == 10.0
    assert CAT_BUILD not in walls
    # extent [0, 30], gap (12, 20) -> idle
    assert wall_extent_us(spans) == 30.0
    assert walls["idle"] == pytest.approx(8.0)
    s = summarize(spans)
    assert s["schema"] == 1 and s["span_count"] == 5
    assert sum(s["fractions"].values()) == pytest.approx(1.0)
    assert s["decisions"] == [{"name": "d"}]


def test_summarize_empty():
    s = summarize([])
    assert s["wall_us"] == 0.0 and s["span_count"] == 0


# --------------------------------------------------- schedule decisions --

def test_record_resolution_null_and_live():
    from repro.kernels.schedule import record_resolution

    record_resolution(None, plan="halo", steps_per_launch=4, pipeline=True)
    record_resolution(NULL_TRACER, plan="halo", steps_per_launch=4,
                      pipeline=True)  # both no-ops, no error
    tr = Tracer()
    record_resolution(tr, plan="halo", steps_per_launch=4, pipeline=True,
                      reason="covering rule", pattern="stencil_1d")
    (s,) = tr.spans
    assert s.category == CAT_DECISION and s.name == "schedule.resolve"
    assert s.attrs["plan"] == "halo"
    assert s.attrs["steps_per_launch"] == 4
    assert s.attrs["pipeline"] is True
    assert s.attrs["reason"] == "covering rule"
    assert s.attrs["cost_model_source"] in ("analytic", "measured", "env")
    assert s.attrs["exchange_row_steps"] > 0


# ------------------------------------------------------ traced executors --

def _graph(pattern, **kw):
    from repro.core import KernelSpec, TaskGraph

    base = dict(steps=6, width=16, payload=8,
                kernel=KernelSpec("compute_bound", 8), radius=1, seed=3)
    base.update(kw)
    return TaskGraph(pattern=pattern, **base)


BACKEND_CASES = [
    ("fused", "stencil_1d", {}),
    ("serialized", "stencil_1d", {}),
    ("bsp", "stencil_1d", {}),
    ("bsp", "fft", {}),
    ("bsp", "spread", {}),
    ("bsp_scan", "stencil_1d", {}),
    ("overlap", "stencil_1d", {}),
]


@pytest.mark.parametrize("name,pattern,opts", BACKEND_CASES,
                         ids=[f"{n}-{p}" for n, p, _ in BACKEND_CASES])
def test_traced_matches_execute(name, pattern, opts):
    from repro.core import get_runtime

    g = _graph(pattern)
    ref = get_runtime(name, **opts).execute(g)
    rt = get_runtime(name, trace=True, **opts)
    out = rt.trace_once(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    s = summarize(rt.tracer.spans)
    assert s["span_count"] > 0 and s["wall_us"] > 0
    assert sum(s["fractions"].values()) == pytest.approx(1.0)
    assert s["fractions"]["dispatch"] > 0  # every backend dispatches


PALLAS_CASES = [
    ("halo-S1", "stencil_1d", {}, dict()),
    ("blocked-serial", "stencil_1d", {},
     dict(steps_per_launch=2, pipeline=False)),
    ("blocked-pipelined", "stencil_1d", {"width": 32},
     dict(steps_per_launch=2)),
    ("stride", "fft", {}, dict()),
    ("allgather-step", "spread", {}, dict()),
    ("allgather-blocked", "spread", {}, dict(steps_per_launch=2)),
    ("allgather-period1", "all_to_all", {}, dict()),
]


@pytest.mark.parametrize("label,pattern,gkw,opts", PALLAS_CASES,
                         ids=[c[0] for c in PALLAS_CASES])
def test_pallas_step_traced_matches_execute(label, pattern, gkw, opts):
    """Every traced pallas_step plan path is bit-compatible with the
    production executor AND records a plan decision and its layer spans:
    the build (non-wall, with the resolved plan) and the traced call."""
    from repro.core import get_runtime

    g = _graph(pattern, **gkw)
    ref = get_runtime("pallas_step", **opts).execute(g)
    rt = get_runtime("pallas_step", trace=True, **opts)
    out = rt.trace_once(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    s = summarize(rt.tracer.spans)
    assert s["decisions"], "schedule decision record missing"
    d = s["decisions"][0]
    assert d["name"] == "schedule.resolve"
    assert d["plan"] in ("halo", "stride", "allgather")
    assert d["runtime"] == "pallas_step"
    spans = {sp.name: sp for sp in rt.tracer.spans}
    assert spans["pallas_step.build"].category == CAT_BUILD
    assert spans["pallas_step.build"].attrs["plan"] == d["plan"]
    assert spans["pallas_step.call"].category == "dispatch"


def test_trace_once_null_tracer_is_plain_execute():
    from repro.core import get_runtime

    g = _graph("stencil_1d")
    rt = get_runtime("bsp")
    assert rt.tracer is NULL_TRACER
    ref = rt.execute(g)
    out = rt.trace_once(g)
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    assert rt.tracer.spans == ()


def test_trace_once_warmup_does_not_duplicate_spans():
    """trace_once runs a warmup (compile) pass and rolls its spans back:
    two consecutive summaries must agree on the span count."""
    from repro.core import get_runtime

    g = _graph("stencil_1d")
    rt = get_runtime("serialized", trace=True)
    rt.trace_once(g)
    n1 = len(rt.tracer.spans)
    rt.tracer.clear()
    rt.trace_once(g)
    assert len(rt.tracer.spans) == n1


# ------------------------------------------------------------ layer spans --

LAYER_SPANS = ("pallas_step.build", "pallas_step.plan",
               "pallas_step.operands", "pallas_step.program",
               "pallas_step.first_call", "pallas_step.call")


def _host_events(trace_dir):
    """(name, start_ns, end_ns) of the layer spans on the profiler's host
    planes of the one trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = list((trace_dir).rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    return sorted(
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name in LAYER_SPANS)


def test_pallas_step_layer_spans_land_on_the_profiler_host_plane(tmp_path):
    """Under a profiler session, one build and two calls of a halo graph
    leave the layer spans on the host plane, named exactly, nested and in
    time order: build holding plan < operands < program, then first_call,
    then call."""
    import jax

    from repro.core import get_runtime
    from repro.core.task_kernels import initial_state

    g = _graph("stencil_1d")
    init = initial_state(g.width, g.payload, g.seed)
    rt = get_runtime("pallas_step")
    with jax.profiler.trace(str(tmp_path)):
        fn = rt.build(g)
        jax.block_until_ready(fn(init))
        jax.block_until_ready(fn(init))
    ev = {}
    for name, start, end in _host_events(tmp_path):
        assert name not in ev, f"{name} recorded twice"
        ev[name] = (start, end)
    assert set(ev) == set(LAYER_SPANS)
    b0, b1 = ev["pallas_step.build"]
    inner = [ev[n] for n in ("pallas_step.plan", "pallas_step.operands",
                             "pallas_step.program")]
    assert all(b0 <= s <= e <= b1 for s, e in inner)
    assert [s for s, _ in inner] == sorted(s for s, _ in inner)
    assert inner[0][1] <= inner[1][0] and inner[1][1] <= inner[2][0]
    assert b1 <= ev["pallas_step.first_call"][0]
    assert ev["pallas_step.first_call"][1] <= ev["pallas_step.call"][0]


@pytest.mark.parametrize("label,pattern,gkw,opts", PALLAS_CASES,
                         ids=[c[0] for c in PALLAS_CASES])
def test_pallas_step_layer_counters(label, pattern, gkw, opts):
    """On every plan path, one build and two calls count build, operands,
    first_call and call once each, each with time spent, with tracing
    off, and the built program still computes what execute does."""
    import jax

    from repro.core import get_runtime
    from repro.core.task_kernels import initial_state

    g = _graph(pattern, **gkw)
    ref = get_runtime("pallas_step", **opts).execute(g)
    init = initial_state(g.width, g.payload, g.seed)
    rt = get_runtime("pallas_step", **opts)
    assert rt.tracer is NULL_TRACER
    reset_counters()
    fn = rt.build(g)
    jax.block_until_ready(fn(init))
    out = np.asarray(jax.block_until_ready(fn(init)))
    c = counters()
    names = ("pallas_step.build", "pallas_step.operands",
             "pallas_step.first_call", "pallas_step.call")
    assert {n: c[n][0] for n in names} == dict.fromkeys(names, 1)
    assert all(c[n][1] > 0 for n in names)
    np.testing.assert_array_equal(out, ref)


def test_traced_parity_two_devices_subprocess():
    """The 2-device matrix: real ppermute/all-gather transports under every
    traced plan path, vs production execute."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_COST_MODEL"] = "off"
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import TaskGraph, KernelSpec, get_runtime
        from repro.obs import summarize

        def g(pattern, **kw):
            base = dict(steps=6, width=16, payload=8,
                        kernel=KernelSpec("compute_bound", 8), radius=1,
                        seed=3)
            base.update(kw)
            return TaskGraph(pattern=pattern, **base)

        cases = [
            ("pallas_step", g("stencil_1d"), {}),
            ("pallas_step", g("stencil_1d"),
             dict(steps_per_launch=2, pipeline=False)),
            ("pallas_step", g("stencil_1d", width=32),
             dict(steps_per_launch=2)),
            ("pallas_step", g("fft"), {}),
            ("pallas_step", g("spread"), {}),
            ("pallas_step", g("spread"), dict(steps_per_launch=2)),
            ("bsp", g("stencil_1d"), {}),
            ("overlap", g("stencil_1d"), {}),
        ]
        for name, graph, opts in cases:
            ref = get_runtime(name, **opts).execute(graph)
            rt = get_runtime(name, trace=True, **opts)
            out = rt.trace_once(graph)
            assert np.allclose(ref, out, rtol=1e-5, atol=1e-6), (
                name, graph.pattern, opts)
            s = summarize(rt.tracer.spans)
            assert s["span_count"] > 0 and s["wall_us"] > 0
        print("ALL OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, (
        f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
    assert "ALL OK" in out.stdout
