"""Measured cost model (kernels/probes.py): precedence, cache codec,
resolver parity, plan re-routing.

Three contracts keep the autotuner honest:

  precedence   explicit model > REPRO_PIPELINE_EXCHANGE_ROW_STEPS env >
               cached probes (REPRO_COST_MODEL) > analytic fallback —
               locked here so a cached calibration can never shadow a
               deliberate env override, and an explicit model always wins.
  parity       a MEASURED model whose exchange_row_steps equals the
               analytic constant makes every depth resolver decide
               IDENTICALLY to the analytic fallback across a shape grid —
               measurement refines the constants, never the rules.
  re-routing   only a measured model may flip a butterfly's "auto" from
               the per-step stride plan to the blocked all-gather plan,
               the verdict reason names the measured numbers, and the
               re-routed schedule stays bit-compatible with fused.

conftest pins REPRO_COST_MODEL=off so the ambient cache can't leak in;
tests that need a cache point the env at a tmp_path file.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import KernelSpec, TaskGraph, get_runtime
from repro.kernels import probes
from repro.kernels import schedule


def graph(pattern, **kw):
    base = dict(steps=6, width=16, payload=8,
                kernel=KernelSpec("compute_bound", 8), radius=2, seed=3)
    base.update(kw)
    return TaskGraph(pattern=pattern, **base)


def measured(**kw):
    """A fully-populated measured model (rankable unless overridden)."""
    base = dict(
        source="measured", exchange_row_steps=512.0, launch_us=50.0,
        row_step_us=0.1, halo_exchange_us={"xla": 51.2},
        stride_exchange_us={"xla": 40.0}, gather_us={64: 30.0, 512: 90.0},
        platform=probes._platform(), devices=1, payload=8)
    base.update(kw)
    return probes.CostModel(**base)


# ------------------------------------------------------------- cache codec


def test_cache_round_trip_and_merge(tmp_path):
    path = tmp_path / "cm.json"
    m1 = measured(payload=8)
    probes.save_cost_model(m1, path)
    loaded = probes.load_cost_model(path)
    assert loaded == {m1.cache_key(): m1}
    # gather widths survive the str->int JSON round trip exactly
    assert loaded[m1.cache_key()].gather_us == {64: 30.0, 512: 90.0}
    # a second calibration MERGES (different payload = different key)
    m2 = measured(payload=128)
    probes.save_cost_model(m2, path)
    loaded = probes.load_cost_model(path)
    assert set(loaded) == {m1.cache_key(), m2.cache_key()}
    assert loaded[m1.cache_key()] == m1
    # recalibrating an existing key REPLACES it
    m1b = dataclasses.replace(m1, launch_us=99.0)
    probes.save_cost_model(m1b, path)
    assert probes.load_cost_model(path)[m1.cache_key()].launch_us == 99.0


def test_cache_rejects_corruption_loudly(tmp_path):
    path = tmp_path / "cm.json"
    path.write_text("{ not json")
    with pytest.raises(ValueError, match="corrupt"):
        probes.load_cost_model(path)
    path.write_text(json.dumps({"schema": 999, "entries": {}}))
    with pytest.raises(ValueError, match="schema"):
        probes.load_cost_model(path)
    entry = measured().to_dict()
    entry["mystery_field"] = 1
    path.write_text(json.dumps(
        {"schema": probes.SCHEMA_VERSION, "entries": {"k": entry}}))
    with pytest.raises(ValueError, match="corrupt"):
        probes.load_cost_model(path)


def test_match_entry_platform_devices_payload():
    a = measured(devices=2, payload=8)
    b = measured(devices=2, payload=128)
    other = measured(devices=4, payload=8)
    alien = measured(platform="tpu", devices=2, payload=8)
    entries = {m.cache_key(): m for m in (a, b, other, alien)}
    plat = probes._platform()
    # device count must match exactly; payload picks the nearest probe
    assert probes._match_entry(entries, plat, 2, 8) == a
    assert probes._match_entry(entries, plat, 2, 100) == b
    assert probes._match_entry(entries, plat, 4, 999) == other
    assert probes._match_entry(entries, plat, 8, 8) is None
    assert probes._match_entry(entries, "rocm", 2, 8) is None


# -------------------------------------------------------------- precedence


def test_precedence_cached_beats_analytic(tmp_path, monkeypatch):
    path = tmp_path / "cm.json"
    probes.save_cost_model(measured(exchange_row_steps=777.0), path)
    monkeypatch.setenv(probes.COST_MODEL_ENV, str(path))
    m = probes.default_cost_model(devices=1, payload=8)
    assert m.source == "measured" and m.exchange_row_steps == 777.0
    assert schedule.exchange_row_steps() == 777.0


def test_precedence_env_beats_cache(tmp_path, monkeypatch):
    path = tmp_path / "cm.json"
    probes.save_cost_model(measured(exchange_row_steps=777.0), path)
    monkeypatch.setenv(probes.COST_MODEL_ENV, str(path))
    monkeypatch.setenv(schedule._EXCHANGE_ROW_STEPS_ENV, "99")
    m = probes.default_cost_model(devices=1, payload=8)
    assert m.source == "env" and m.exchange_row_steps == 99.0
    assert schedule.exchange_row_steps() == 99.0
    # an env model is NOT measured: it carries the constant, nothing else
    assert not m.can_rank_plans


def test_precedence_explicit_beats_env(monkeypatch):
    monkeypatch.setenv(schedule._EXCHANGE_ROW_STEPS_ENV, "99")
    explicit = measured(exchange_row_steps=321.0)
    assert schedule.exchange_row_steps(explicit) == 321.0
    # ... and the resolvers thread it through
    assert schedule.gathered_pays_off(16, 16, 4, model=explicit)


def test_precedence_off_pins_analytic(monkeypatch):
    monkeypatch.setenv(probes.COST_MODEL_ENV, "off")
    m = probes.default_cost_model()
    assert m.source == "analytic"
    assert m.exchange_row_steps == schedule.PIPELINE_EXCHANGE_ROW_STEPS
    assert not m.can_rank_plans


def test_env_override_invalid_fails_loudly(monkeypatch):
    monkeypatch.setenv(schedule._EXCHANGE_ROW_STEPS_ENV, "-3")
    with pytest.raises(ValueError, match="positive"):
        schedule.exchange_row_steps()
    monkeypatch.setenv(schedule._EXCHANGE_ROW_STEPS_ENV, "lots")
    with pytest.raises(ValueError):
        schedule.exchange_row_steps()


def test_coerce_cost_model_forms(tmp_path):
    m = measured()
    assert probes.coerce_cost_model(m) is m
    assert probes.coerce_cost_model(m.to_dict()) == m
    path = tmp_path / "cm.json"
    probes.save_cost_model(m, path)
    assert probes.coerce_cost_model(str(path), devices=1, payload=8) == m
    with pytest.raises(ValueError, match="no entry"):
        probes.coerce_cost_model(str(path), devices=64)
    with pytest.raises(TypeError):
        probes.coerce_cost_model(3.14)


# ----------------------------------------------------------------- queries


def test_gather_us_at_interpolates_and_extrapolates():
    m = measured(gather_us={64: 30.0, 512: 90.0})
    assert m.gather_us_at(64) == 30.0
    assert m.gather_us_at(512) == 90.0
    assert m.gather_us_at(288) == pytest.approx(60.0)  # midpoint
    # end-slope extrapolation, clamped at zero below the first point
    assert m.gather_us_at(1024) == pytest.approx(158.57, abs=0.1)
    assert m.gather_us_at(1) >= 0.0
    assert measured(gather_us={64: 30.0}).gather_us_at(512) == 30.0
    assert measured(gather_us={}).gather_us_at(64) is None


def test_stride_us_for_fallback():
    m = measured(stride_exchange_us={"xla": 40.0, "ppermute": 25.0})
    assert m.stride_us_for("xla") == 40.0
    assert m.stride_us_for("shmem") == 25.0  # any probed transport
    assert measured(stride_exchange_us={}).stride_us_for("xla") is None


def test_describe_names_the_verdict_source():
    assert "analytic fallback" in probes.analytic_cost_model().describe()
    env = probes.CostModel(source="env", exchange_row_steps=99.0)
    assert schedule._EXCHANGE_ROW_STEPS_ENV in env.describe()
    d = measured().describe(width=64)
    for needle in ("measured on", "launch=", "gather=30.0us@w64", "->"):
        assert needle in d, d


# ------------------------------------------------- parity with the analytic


PARITY_SHAPES = [
    dict(block=b, radius=r, payload=p)
    for b in (32, 64, 256, 1024) for r in (1, 2, 4) for p in (8, 64, 512)
]


def test_depth_resolver_parity_measured_vs_analytic():
    """A measured model with the analytic exchange constant decides
    exactly like the analytic fallback everywhere — proof that wiring the
    model through the resolvers changed WHO supplies the constant, not
    the rules. (This is what keeps a cacheless run bit-identical.)"""
    analytic = probes.analytic_cost_model()
    twin = measured(
        exchange_row_steps=float(schedule.PIPELINE_EXCHANGE_ROW_STEPS))
    for shape in PARITY_SHAPES:
        for s in (1, 2, 4, 8, 16):
            assert (schedule.pipeline_interior_covers_exchange(
                        shape["block"], shape["radius"], s, model=analytic)
                    == schedule.pipeline_interior_covers_exchange(
                        shape["block"], shape["radius"], s, model=twin)), shape
        for pipeline in (False, True):
            assert (schedule.choose_steps_per_launch(
                        **shape, total_steps=33, pipeline=pipeline,
                        model=analytic)
                    == schedule.choose_steps_per_launch(
                        **shape, total_steps=33, pipeline=pipeline,
                        model=twin)), shape
    for width, block in [(16, 16), (64, 32), (512, 64), (2048, 256)]:
        for s in (2, 4, 8, 16):
            assert (schedule.gathered_pays_off(width, block, s,
                                               model=analytic)
                    == schedule.gathered_pays_off(width, block, s,
                                                  model=twin))
        assert (schedule.choose_steps_per_launch_gathered(
                    width=width, block=block, max_deps=2, payload=64,
                    total_steps=33, model=analytic)
                == schedule.choose_steps_per_launch_gathered(
                    width=width, block=block, max_deps=2, payload=64,
                    total_steps=33, model=twin))


# -------------------------------------------------------- plan re-routing


def test_gathered_beats_strides_analytic_always_declines():
    ok, why = schedule.gathered_beats_strides(
        width=64, block=64, steps_per_launch=4, off_block_strides=0,
        period=6, model=probes.analytic_cost_model())
    assert not ok
    assert "analytic fallback" in why


def test_gathered_beats_strides_ranks_measured_walls():
    # expensive launches + cheap gather: amortizing S launches wins
    win = measured(launch_us=500.0, row_step_us=0.01, gather_us={64: 50.0})
    ok, why = schedule.gathered_beats_strides(
        width=64, block=64, steps_per_launch=4, off_block_strides=3,
        period=6, model=win)
    assert ok
    for needle in ("measured:", "launch=500.0us", "gather=50.0us@w64"):
        assert needle in why, why
    # monstrous gather: per-step strides stay
    lose = measured(launch_us=1.0, gather_us={64: 100000.0})
    ok, why = schedule.gathered_beats_strides(
        width=64, block=64, steps_per_launch=4, off_block_strides=3,
        period=6, model=lose)
    assert not ok and "measured:" in why
    # off-block strides with no stride probe: unrankable, decline
    ok, why = schedule.gathered_beats_strides(
        width=64, block=32, steps_per_launch=4, off_block_strides=3,
        period=6, model=measured(stride_exchange_us={}))
    assert not ok and "stride-exchange" in why


def test_auto_reroutes_butterfly_under_winning_model():
    """The new capability: a measured model that prices per-step stride
    launches above the amortized gather re-routes "auto" to the blocked
    all-gather plan — and the numerics stay bit-compatible with fused."""
    g = graph("fft", width=64, steps=9)
    win = measured(launch_us=500.0, row_step_us=0.01, gather_us={64: 50.0})
    rt = get_runtime("pallas_step", steps_per_launch="auto", cost_model=win)
    plan = rt._schedule_for_graph(g)
    assert plan.kind == "allgather" and plan.steps_per_launch > 1
    assert plan.reason.startswith("measured:")
    # fewer launches than the per-step stride plan would pay
    stride_rt = get_runtime("pallas_step", steps_per_launch=1,
                            cost_model=win)
    assert rt.dispatches_per_run(g) < stride_rt.dispatches_per_run(g)
    ref = get_runtime("fused").execute(g)
    np.testing.assert_allclose(rt.execute(g), ref, rtol=1e-5, atol=1e-6)


def test_auto_keeps_stride_when_model_declines():
    g = graph("fft", width=64, steps=9)
    # losing measured model: verdict recorded, plan unchanged
    lose = measured(launch_us=1.0, gather_us={64: 100000.0})
    plan = get_runtime("pallas_step", steps_per_launch="auto",
                       cost_model=lose)._schedule_for_graph(g)
    assert plan.kind == "stride" and plan.steps_per_launch == 1
    assert "measured:" in plan.reason
    # analytic fallback (conftest pins REPRO_COST_MODEL=off): the
    # pre-measurement behavior, with the source named in the reason
    plan = get_runtime("pallas_step",
                       steps_per_launch="auto")._schedule_for_graph(g)
    assert plan.kind == "stride" and plan.steps_per_launch == 1
    assert "analytic fallback" in plan.reason


def test_rejection_message_names_verdict_source():
    rt = get_runtime("pallas_step", gather_width_cap=64)
    ok, why = rt.supports(graph("spread", width=128))
    assert not ok
    assert "verdict source" in why and "analytic fallback" in why


def test_explicit_blocked_butterfly_routing_unchanged():
    """The pre-existing explicit-depth re-route neither needs nor
    consults a measured model — it stays under the analytic fallback."""
    g = graph("fft", width=64, steps=9)
    plan = get_runtime("pallas_step",
                       steps_per_launch=4)._schedule_for_graph(g)
    assert plan.kind == "allgather" and plan.steps_per_launch == 4
    assert plan.reason == "explicit blocked request"


# ------------------------------------------------------------------ probes


def test_run_probes_structure_and_round_trip(tmp_path):
    """Single-device smoke probes: every cost positive and finite, the
    stride probe skipped (no partner), and save/load reproduces the model
    EXACTLY (the calibration a run records is the calibration a later run
    resolves)."""
    m = probes.run_probes(devices=1, payload=8, smoke=True)
    assert m.source == "measured" and m.devices == 1 and m.payload == 8
    assert m.platform == probes._platform()
    for v in (m.exchange_row_steps, m.launch_us, m.row_step_us):
        assert np.isfinite(v) and v > 0
    assert set(m.halo_exchange_us) and all(
        v > 0 for v in m.halo_exchange_us.values())
    assert m.stride_exchange_us == {}  # single device: no XOR partner
    assert m.gather_us and all(v > 0 for v in m.gather_us.values())
    assert m.can_rank_plans
    path = probes.save_cost_model(m, tmp_path / "cm.json")
    assert probes.load_cost_model(path)[m.cache_key()] == m


# ------------------------------------- gather transport choice (PR 9)


def test_gather_impl_us_codec_round_trip():
    """The devices-dimension probes survive JSON (string keys at both
    nested int levels) and stay OPTIONAL: a pre-PR-9 dict without the
    field loads as an empty table under the same schema."""
    m = measured(devices=16,
                 gather_impl_us={"xla": {16: {64: 900.0, 256: 1100.0}},
                                 "chunked": {16: {64: 500.0}, 8: {64: 450.0}}})
    r = probes.CostModel.from_dict(json.loads(json.dumps(m.to_dict())))
    assert r == m
    assert r.gather_walls_at(64, 16) == {"xla": 900.0, "chunked": 500.0}
    # exact-device-match rule: D=8 only has the chunked probe
    assert r.gather_walls_at(64, 8) == {"chunked": 450.0}
    assert r.gather_walls_at(64, 4) == {}
    legacy = {k: v for k, v in m.to_dict().items() if k != "gather_impl_us"}
    assert probes.CostModel.from_dict(legacy).gather_impl_us == {}


def test_choose_gather_impl_measured_ranks_walls():
    m = measured(devices=16,
                 gather_impl_us={"xla": {16: {64: 900.0}},
                                 "chunked": {16: {64: 500.0}}})
    impl, why = schedule.choose_gather_impl(width=64, devices=16, model=m)
    assert impl == "chunked"
    for needle in ("measured", "chunked=500.0us", "xla=900.0us"):
        assert needle in why, why
    # the measured table outranks the structural rule in BOTH directions
    m2 = measured(devices=16,
                  gather_impl_us={"xla": {16: {64: 400.0}},
                                  "chunked": {16: {64: 500.0}}})
    impl, _ = schedule.choose_gather_impl(width=64, devices=16, model=m2)
    assert impl == "xla"


def test_choose_gather_impl_structural_crossover():
    """No devices-dimension probes -> the structural rule: monolithic
    below D=16, chunked at and above, and the reason says why."""
    for d, want in [(2, "xla"), (8, "xla"), (16, "chunked"),
                    (64, "chunked")]:
        impl, why = schedule.choose_gather_impl(width=256, devices=d,
                                                model=measured())
        assert impl == want, (d, impl, why)
    _, why = schedule.choose_gather_impl(width=256, devices=16,
                                         model=measured())
    assert "sqrt(D)" in why


def test_choose_member_shards_analytic_keeps_replicated():
    dk, why = schedule.choose_member_shards(devices=8, num_members=4,
                                            width=64)
    assert dk == 1
    assert "analytic" in why


def test_choose_member_shards_measured_prices_split():
    """With a measured model, sharding K divides the moved halo rows, so
    the priced argmin picks a real split; candidates that break a row
    ring (Dr < 2) or width divisibility are never offered."""
    m = measured(devices=8)
    dk, why = schedule.choose_member_shards(devices=8, num_members=4,
                                            width=64, steps_per_launch=2,
                                            model=m)
    assert dk == 4  # Dr=2 keeps the ring; the largest K split wins
    assert "measured" in why and "us/launch" in why
    # K=3 shares no divisor > 1 with D=8: no viable split, loud reason
    dk, why = schedule.choose_member_shards(devices=8, num_members=3,
                                            width=64, model=m)
    assert dk == 1 and "no viable" in why


def test_run_probes_smoke_includes_gather_impl_table():
    """run_probes now carries the devices-dimension transport table; on a
    single device it stays empty (nothing to rendezvous)."""
    m = probes.run_probes(devices=1, smoke=True, reps=1)
    assert m.gather_impl_us == {}


def test_platform_lookup_failure_propagates(monkeypatch):
    """No silent "cpu" when the backend lookup fails: a calibration keyed
    to the wrong platform would steer every schedule on the real one."""
    import jax

    def no_backend():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "default_backend", no_backend)
    with pytest.raises(RuntimeError, match="backend init failed"):
        probes._platform()
