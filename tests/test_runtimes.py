"""Cross-backend equivalence — the system's core invariant.

Every runtime backend must produce identical final states for the same task
graph (DESIGN.md §2: the backends differ ONLY in scheduling/communication
strategy, never in dataflow). Single-device here; the multi-device versions
run in test_distributed.py subprocesses.
"""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    GraphEnsemble,
    KernelSpec,
    TaskGraph,
    available_runtimes,
    get_runtime,
)
from repro.core import patterns as _patterns
from repro.core.task_kernels import (
    apply_kernel,
    combine_all_to_all,
    combine_dependencies,
    initial_state,
)

PATTERNS = ["trivial", "no_comm", "stencil_1d", "stencil_1d_periodic", "dom",
            "tree", "fft", "all_to_all", "nearest", "spread",
            "random_nearest"]


def graph(pattern, **kw):
    base = dict(steps=6, width=16, payload=8,
                kernel=KernelSpec("compute_bound", 8), radius=2, seed=3)
    base.update(kw)
    return TaskGraph(pattern=pattern, **base)


def test_registry_contents():
    names = available_runtimes()
    for expected in ("fused", "serialized", "bsp", "bsp_scan", "overlap",
                     "pallas_step"):
        assert expected in names


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("backend", ["serialized", "bsp", "bsp_scan",
                                     "overlap", "pallas_step"])
def test_backend_matches_fused(pattern, backend):
    g = graph(pattern)
    rt = get_runtime(backend)
    ok, why = rt.supports(g)
    if not ok:
        pytest.skip(why)
    ref = get_runtime("fused").execute(g)
    out = rt.execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["compute_bound", "memory_bound", "empty"])
def test_kernel_kinds_run(kind):
    g = graph("stencil_1d", kernel=KernelSpec(kind, 4, scratch=64))
    ref = get_runtime("fused").execute(g)
    out = get_runtime("bsp_scan").execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    assert np.isfinite(ref).all()


def test_single_step_graph():
    g = graph("stencil_1d", steps=1)
    ref = get_runtime("fused").execute(g)
    out = get_runtime("bsp").execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_large_iterations_stay_bounded():
    """Contraction-map FMA: no inf/nan at any grain size (task_kernels)."""
    g = graph("stencil_1d", kernel=KernelSpec("compute_bound", 1 << 14))
    out = get_runtime("fused").execute(g)
    assert np.isfinite(out).all()
    assert np.abs(out).max() < 10.0


def test_overlap_variants_match():
    """Fig-3-style build options must not change semantics."""
    g = graph("stencil_1d")
    ref = get_runtime("fused").execute(g)
    for opts in ({"overlap": False}, {"halo_via": "allgather"},
                 {"unroll": 2}):
        out = get_runtime("overlap", **opts).execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=str(opts))


def test_bsp_donate_toggle():
    g = graph("stencil_1d")
    a = get_runtime("bsp", donate=True).execute(g)
    b = get_runtime("bsp", donate=False).execute(g)
    np.testing.assert_allclose(a, b, rtol=1e-6)


def test_dispatch_accounting():
    g = graph("stencil_1d", steps=7)
    assert get_runtime("fused").dispatches_per_run(g) == 1
    assert get_runtime("bsp").dispatches_per_run(g) == 7
    assert get_runtime("bsp_scan").dispatches_per_run(g) == 1
    # pallas_step reports actual KERNEL LAUNCHES (the overhead its METG
    # floor measures), not host dispatches: one t=0 body-only launch plus
    # ceil((T-1)/S) blocked combine launches. The (default) pipelined
    # schedule pays TWO launches per blocked iteration — boundary +
    # interior — and the accounting stays honest about it.
    assert get_runtime("pallas_step").dispatches_per_run(g) == 7
    assert get_runtime(
        "pallas_step", steps_per_launch=3).dispatches_per_run(g) == 5
    assert get_runtime("pallas_step", steps_per_launch=3,
                       pipeline=False).dispatches_per_run(g) == 3
    assert get_runtime("pallas_step", steps_per_launch=6,
                       pipeline=False).dispatches_per_run(g) == 2
    # depth clamps to the graph's T-1 combine steps (rest is masked tail)
    assert get_runtime("pallas_step", steps_per_launch=100,
                       pipeline=False).dispatches_per_run(g) == 2
    assert get_runtime(
        "pallas_step", steps_per_launch=100).dispatches_per_run(g) == 3
    assert get_runtime(
        "pallas_step").dispatches_per_run(graph("stencil_1d", steps=1)) == 1
    assert get_runtime("serialized").dispatches_per_run(g) == 7 * 16


# ------------------------------------------------ pallas_step (megakernel)


@pytest.mark.parametrize("pattern", list(_patterns.HALO_PATTERNS))
@pytest.mark.parametrize("K", [1, 4])
def test_pallas_step_halo_patterns_ensembles(pattern, K):
    """Acceptance: pallas_step runs every HALO_PATTERNS pattern and matches
    fused per ensemble member for K in {1, 4} (interpret mode)."""
    members = [
        TaskGraph(steps=5, width=16, payload=8, pattern=pattern, radius=2,
                  kernel=KernelSpec("compute_bound", 8), seed=k)
        for k in range(K)
    ]
    ens = GraphEnsemble(members)
    rt = get_runtime("pallas_step")
    ok, why = rt.supports_ensemble(ens)
    assert ok, why
    outs = rt.execute_ensemble(ens)
    for k, (g, out) in enumerate(zip(members, outs)):
        ref = get_runtime("fused").execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{pattern} member {k}")


@pytest.mark.parametrize("combine", ["window", "gather", "onehot"])
def test_pallas_step_combine_modes_match_fused(combine):
    g = graph("nearest")
    ref = get_runtime("fused").execute(g)
    out = get_runtime("pallas_step", combine=combine).execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                               err_msg=combine)


def test_pallas_step_kernel_kinds():
    for kind in ("compute_bound", "memory_bound", "empty"):
        g = graph("stencil_1d", kernel=KernelSpec(kind, 4, scratch=64))
        ref = get_runtime("fused").execute(g)
        out = get_runtime("pallas_step").execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=kind)


HALO_LIKE = list(_patterns.HALO_PATTERNS) + ["random_nearest"]


@pytest.mark.parametrize("pattern", HALO_LIKE)
@pytest.mark.parametrize("S", [3, 8])
def test_pallas_step_blocked_matches_unblocked_and_fused(pattern, S):
    """Temporal blocking is a pure scheduling change: for every halo
    pattern, S steps per launch must be allclose to the S=1 path AND the
    fused oracle (T=7 with S=3 exercises the masked tail: 6 combine steps
    = 2 launches; with S=8 the whole run is one partially-masked launch)."""
    g = graph(pattern, steps=7)
    ref = get_runtime("fused").execute(g)
    s1 = get_runtime("pallas_step").execute(g)
    out = get_runtime("pallas_step", steps_per_launch=S).execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                               err_msg=f"{pattern} S={S} vs fused")
    np.testing.assert_allclose(out, s1, rtol=1e-5, atol=1e-6,
                               err_msg=f"{pattern} S={S} vs S=1")


@pytest.mark.parametrize("combine", ["window", "gather", "onehot"])
def test_pallas_step_blocked_combine_modes_match_fused(combine):
    g = graph("nearest", steps=8)
    ref = get_runtime("fused").execute(g)
    out = get_runtime("pallas_step", combine=combine,
                      steps_per_launch=4).execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                               err_msg=combine)


def test_pallas_step_blocked_kernel_kinds():
    for kind in ("compute_bound", "memory_bound", "empty"):
        g = graph("stencil_1d", kernel=KernelSpec(kind, 4, scratch=64))
        ref = get_runtime("fused").execute(g)
        out = get_runtime("pallas_step", steps_per_launch=3).execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=kind)


@pytest.mark.parametrize("S", [1, 3, 8])
def test_pallas_step_blocked_hetero_steps_ensemble(S):
    """Launch-granularity freezing: members with different T inside one
    blocked stacked ensemble each match running alone under fused (members
    end mid-launch, so the act mask must freeze them at inner-step
    granularity)."""
    members = [
        TaskGraph(steps=t, width=16, payload=8, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), seed=k)
        for k, t in enumerate((3, 6, 1, 5))
    ]
    ens = GraphEnsemble(members)
    assert ens.heterogeneous_steps
    outs = get_runtime("pallas_step", steps_per_launch=S).execute_ensemble(ens)
    for k, (g, out) in enumerate(zip(members, outs)):
        ref = get_runtime("fused").execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"S={S} member {k} T={g.steps}")


def test_pallas_step_blocked_mixed_spec_tuple_ensemble():
    """The mixed-spec (tuple) fallback blocks too: different kernels,
    patterns, and T per member, one shared launch cadence."""
    members = [
        TaskGraph(steps=5, width=16, payload=8, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), seed=0),
        TaskGraph(steps=3, width=16, payload=8, pattern="nearest", radius=2,
                  kernel=KernelSpec("compute_bound", 32), seed=1),
        TaskGraph(steps=7, width=16, payload=8, pattern="no_comm",
                  kernel=KernelSpec("memory_bound", 2, scratch=32), seed=2),
    ]
    ens = GraphEnsemble(members)
    rt = get_runtime("pallas_step", steps_per_launch=4)
    outs = rt.execute_ensemble(ens)
    for k, (g, out) in enumerate(zip(members, outs)):
        ref = get_runtime("fused").execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"member {k}")


def test_pallas_step_deep_halo_exceeding_width_wraps():
    """S*r far beyond W (depth wraps the ring repeatedly) stays exact."""
    g = graph("stencil_1d_periodic", steps=10, width=8)
    ref = get_runtime("fused").execute(g)
    out = get_runtime("pallas_step", steps_per_launch=8).execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_pallas_step_auto_steps_per_launch():
    """'auto' resolves through kernels/schedule.py and stays exact."""
    g = graph("stencil_1d", steps=9)
    ref = get_runtime("fused").execute(g)
    rt = get_runtime("pallas_step", steps_per_launch="auto")
    out = rt.execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    # auto picks a deep schedule for this tiny shape -> few launches
    assert rt.dispatches_per_run(g) < g.steps


# --------------------- pallas_step S=1 halo loop on its tiled carry

#: (pattern, radius, width, payload, body): every halo pattern, H in
#: {0, 1, 2, 5}, widths 20 and 52 (not multiples of 8), payloads 64 and 130
CARRY_CASES = [
    ("stencil_1d", 1, 52, 64, "compute_bound"),
    ("stencil_1d_periodic", 1, 52, 130, "compute_bound"),
    ("dom", 1, 20, 64, "compute_bound"),
    ("nearest", 2, 52, 64, "compute_bound"),
    ("nearest", 5, 20, 130, "compute_bound"),
    ("random_nearest", 2, 52, 130, "compute_bound"),
    ("no_comm", 1, 20, 130, "compute_bound"),
    ("trivial", 1, 52, 64, "compute_bound"),
    ("stencil_1d", 1, 20, 130, "memory_bound"),
    ("nearest", 2, 52, 64, "empty"),
]
#: the chip's (sublanes, lanes) tile, run in interpret mode
CHIP_TILE = (8, 128)


def _planted_init(width, payload, seed):
    """A seeded state with an inf, a -inf and a NaN inside it: a value
    from outside the window, or a padded row read at a nonzero weight,
    shows as a changed class."""
    x = np.array(initial_state(width, payload, seed))
    rows = np.random.default_rng(seed).choice(width, 3, replace=False)
    x[rows, [0, payload // 2, payload - 1]] = [np.inf, -np.inf, np.nan]
    return x


def _check_carry_parity(pattern, radius, width, payload, body, *,
                        tile=None, devices=None):
    """The S=1 window loop on its halo-extended carry (chip tiling or
    interpret mode's (1, 1)) against the per-step extend branch of the
    same builder, bit for bit."""
    g = TaskGraph(steps=6, width=width, payload=payload, pattern=pattern,
                  radius=radius, seed=5,
                  kernel=KernelSpec(body, 4, scratch=2 * payload))
    x = _planted_init(width, payload, 5)
    rt = get_runtime("pallas_step", devices=devices)
    out = np.asarray(rt._build_halo_stacked(g, 1, tile=tile)(x))
    ref = np.asarray(rt._build_halo_stacked(g, 1, carry=False)(x))
    np.testing.assert_array_equal(out, ref, err_msg=f"{g.describe()} {tile}")


def _check_carry_parity_ensemble(hetero, *, tile=None, devices=None):
    """A stacked K=4 ensemble at S=1 on the carry, each member bit for bit
    against running alone on the per-step extend path."""
    steps = (3, 6, 1, 5) if hetero else (6,) * 4
    members = [
        TaskGraph(steps=t, width=52, payload=64, pattern=p, radius=1,
                  kernel=KernelSpec("compute_bound", 4), seed=k)
        for k, (p, t) in enumerate(zip(
            ("stencil_1d", "dom", "stencil_1d_periodic", "nearest"), steps))
    ]
    inits = [_planted_init(52, 64, k) for k in range(4)]
    rt = get_runtime("pallas_step", devices=devices)
    outs = rt._build_halo_stacked(GraphEnsemble(members), 1, tile=tile)(
        [jnp.asarray(x) for x in inits])
    for k, (g, x, out) in enumerate(zip(members, inits, outs)):
        ref = rt._build_halo_stacked(g, 1, carry=False)(x)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref),
            err_msg=f"member {k} T={g.steps} {tile}")


@pytest.mark.parametrize("tile", [None, CHIP_TILE], ids=["interp", "chip"])
@pytest.mark.parametrize("case", CARRY_CASES,
                         ids=[f"{c[0]}-r{c[1]}-w{c[2]}-p{c[3]}-{c[4]}"
                              for c in CARRY_CASES])
def test_pallas_step_s1_carry_bit_identical(case, tile):
    _check_carry_parity(*case, tile=tile)


@pytest.mark.parametrize("tile", [None, CHIP_TILE], ids=["interp", "chip"])
@pytest.mark.parametrize("hetero", [False, True], ids=["uniform", "hetero"])
def test_pallas_step_s1_carry_stacked_ensemble_bit_identical(hetero, tile):
    _check_carry_parity_ensemble(hetero, tile=tile)


#: (id, runtime options, S, pipelined, carry tile): each branch of the
#: halo builder
ONE_MEMBER_CASES = [
    ("s1-carry-interp", {}, 1, False, None),
    ("s1-carry-chip", {}, 1, False, CHIP_TILE),
    ("s1-extend-onehot", dict(combine="onehot"), 1, False, None),
    ("s3-pipelined", dict(steps_per_launch=3), 3, True, None),
    ("s3-serial", dict(steps_per_launch=3, pipeline=False), 3, False, None),
]


@pytest.mark.parametrize("opts,S,piped,tile",
                         [c[1:] for c in ONE_MEMBER_CASES],
                         ids=[c[0] for c in ONE_MEMBER_CASES])
def test_pallas_step_single_graph_equals_one_member_ensemble(opts, S, piped,
                                                             tile):
    """A single graph's program and a one-member ensemble's come from one
    builder and agree bit for bit on every branch; the chip tile is
    reached through the builder itself, the other cases through
    ``build`` and ``build_ensemble``."""
    g = TaskGraph(steps=8, width=52, payload=64, pattern="nearest",
                  radius=2, seed=3,
                  kernel=KernelSpec("compute_bound", 4, scratch=128))
    x = _planted_init(52, 64, 3)
    rt = get_runtime("pallas_step", **opts)
    assert rt._schedule_for_graph(g) == ("halo", S, "")
    assert rt._pipeline_active(52, S, 2, 64) == piped
    if tile is None:
        out = rt.build(g)(x)
        ens = rt.build_ensemble(GraphEnsemble([g]))([jnp.asarray(x)])[0]
    else:
        out = rt._build_halo_stacked(g, S, tile=tile)(x)
        ens = rt._build_halo_stacked(GraphEnsemble([g]), S, tile=tile)(
            [jnp.asarray(x)])[0]
    assert out.shape == ens.shape == x.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ens))


@pytest.mark.parametrize("tile", [None, CHIP_TILE], ids=["interp", "chip"])
def test_pallas_step_s1_carry_bit_identical_4_devices(tile):
    """The same parity on 4 forced host devices (a subprocess): real ring
    exchanges into the halo blocks, 13-row blocks, and a halo of 5 rows
    past a 5-row block (the multi-hop exchange)."""
    code = f"""
        import sys, jax
        sys.path.insert(0, {str(Path(__file__).parent)!r})
        import test_runtimes as t
        devs = jax.devices()[:4]
        for case in t.CARRY_CASES + [("nearest", 5, 20, 64, "compute_bound")]:
            t._check_carry_parity(*case, tile={tile!r}, devices=devs)
        for hetero in (False, True):
            t._check_carry_parity_ensemble(hetero, tile={tile!r}, devices=devs)
        print("OK")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).parents[1] / "src"))
    done = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert done.returncode == 0 and "OK" in done.stdout, done.stderr[-4000:]


# ----------------------------- pallas_step pipelined deep-halo exchange


@pytest.mark.parametrize("pattern", HALO_LIKE)
@pytest.mark.parametrize("S", [1, 3, 8])
def test_pallas_step_pipeline_bit_identical_to_ablation(pattern, S):
    """The pipelined schedule is a pure dataflow reshuffle: for every halo
    pattern and S in {1, 3, 8}, pipeline=True must be BIT-identical to the
    pipeline=False ablation and allclose to fused. Width 48 keeps a
    nonempty interior at every depth (r=1 patterns at S=8: 48 > 16; r=2:
    48 > 32), so the pipelined path actually engages for S > 1."""
    g = graph(pattern, width=48, steps=10)
    ref = get_runtime("fused").execute(g)
    on = get_runtime("pallas_step", steps_per_launch=S).execute(g)
    off = get_runtime(
        "pallas_step", steps_per_launch=S, pipeline=False).execute(g)
    np.testing.assert_allclose(on, ref, rtol=1e-5, atol=1e-6,
                               err_msg=f"{pattern} S={S} vs fused")
    assert np.array_equal(on, off), f"{pattern} S={S}: pipeline changed bits"


def test_pallas_step_pipeline_halo_impls_bit_identical():
    """Both edge-exchange transports (fused single-collective vs
    per-direction ppermute) move exact row copies; outputs must not differ
    by a bit. Unknown impls fail loudly."""
    g = graph("stencil_1d", width=48, steps=10)
    a = get_runtime("pallas_step", steps_per_launch=4).execute(g)
    b = get_runtime("pallas_step", steps_per_launch=4,
                    halo_impl="ppermute").execute(g)
    assert np.array_equal(a, b)
    with pytest.raises(ValueError, match="halo async impl"):
        get_runtime("pallas_step", steps_per_launch=4,
                    halo_impl="smoke_signals").execute(g)


@pytest.mark.parametrize("S", [3, 4])
def test_pallas_step_pipeline_hetero_stacked_ensemble(S):
    """Pipelined stacked ensembles keep launch-granularity freezing exact:
    members with different T (ending mid-launch) each match fused, and the
    whole run is bit-identical to the serial-exchange ablation."""
    members = [
        TaskGraph(steps=t, width=48, payload=8, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), seed=k)
        for k, t in enumerate((3, 10, 6, 1))
    ]
    ens = GraphEnsemble(members)
    assert ens.heterogeneous_steps
    on = get_runtime(
        "pallas_step", steps_per_launch=S).execute_ensemble(ens)
    off = get_runtime("pallas_step", steps_per_launch=S,
                      pipeline=False).execute_ensemble(ens)
    for k, (g, a, b) in enumerate(zip(members, on, off)):
        ref = get_runtime("fused").execute(g)
        np.testing.assert_allclose(a, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"S={S} member {k} T={g.steps}")
        assert np.array_equal(a, b), f"S={S} member {k}: pipeline changed bits"


def test_pallas_step_pipeline_tuple_mixed_applicability():
    """The tuple path pipelines per member: a no_comm member (halo 0) and a
    wide-halo member share one cadence with a pipelined stencil member, and
    every member still matches fused."""
    members = [
        TaskGraph(steps=9, width=48, payload=8, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), seed=0),
        TaskGraph(steps=5, width=48, payload=8, pattern="no_comm",
                  kernel=KernelSpec("memory_bound", 2, scratch=32), seed=1),
        TaskGraph(steps=7, width=48, payload=8, pattern="nearest", radius=4,
                  kernel=KernelSpec("compute_bound", 32), seed=2),
    ]
    ens = GraphEnsemble(members)
    for pipe in (True, False):
        outs = get_runtime("pallas_step", steps_per_launch=4,
                           pipeline=pipe).execute_ensemble(ens)
        for k, (g, out) in enumerate(zip(members, outs)):
            ref = get_runtime("fused").execute(g)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                       err_msg=f"pipe={pipe} member {k}")


def test_pallas_step_pipeline_auto_respects_profitability():
    """Under steps_per_launch='auto' the tuner's covering verdict binds:
    a block too small for the interior to cover the exchange runs the
    serial schedule (serial launch counts), while explicit S is an
    ablation choice and pipelines whenever structurally possible."""
    g = graph("stencil_1d", width=64, steps=9)
    auto = get_runtime("pallas_step", steps_per_launch="auto")
    S = auto._graph_steps_per_launch(g)
    assert 64 > 2 * S  # structurally pipelineable ...
    L = 1 + -(-(g.steps - 1) // S)
    assert auto.dispatches_per_run(g) == L  # ... but the tuner found no cover
    explicit = get_runtime("pallas_step", steps_per_launch=S)
    assert explicit.dispatches_per_run(g) == 1 + 2 * (L - 1)  # pipelines anyway


# ------------------------- pallas_step beyond halos: pattern -> plan


def test_pallas_step_plan_dispatch_and_rejection_message():
    """supports() is a pattern->plan dispatch: every paper pattern gets a
    plan at moderate widths, and the rejection (global pattern past the
    gather cap) names the plan kinds and the fused fallback."""
    rt = get_runtime("pallas_step")
    assert rt.plan_for(graph("stencil_1d"))[0] == "halo"
    assert rt.plan_for(graph("random_nearest"))[0] == "halo"
    assert rt.plan_for(graph("fft"))[0] == "stride"
    assert rt.plan_for(graph("tree"))[0] == "stride"
    assert rt.plan_for(graph("spread"))[0] == "allgather"
    assert rt.plan_for(graph("all_to_all"))[0] == "allgather"
    capped = get_runtime("pallas_step", gather_width_cap=64)
    ok, why = capped.supports(graph("spread", width=128))
    assert not ok
    for needle in ("halo", "stride", "allgather", "fused",
                   "gather_width_cap=64"):
        assert needle in why, why
    # butterfly keeps the (per-step) stride plan at ANY width
    ok, _ = capped.supports(graph("fft", width=128))
    assert ok
    # width-1 butterfly degenerates to a self-dependency: no stride plan
    # (its two-dep tables would be wrong) — the all-gather plan runs it
    g1 = graph("fft", width=1)
    assert rt.plan_for(g1)[0] == "allgather"
    out = rt.execute(g1)
    np.testing.assert_array_equal(out, get_runtime("fused").execute(g1))
    # "pair" is the stride plan's INTERNAL lowering, not a runtime option
    # — rejected up front (it would crash the halo operand layout deep in
    # the kernel otherwise), like any unknown mode
    for bad in ("pair", "smoke_signals"):
        with pytest.raises(ValueError, match="combine option"):
            get_runtime("pallas_step", combine=bad).execute(
                graph("stencil_1d"))


@pytest.mark.parametrize("pattern", ["stencil_1d", "spread"])
def test_pallas_step_gather_refused_on_tpu(pattern, monkeypatch):
    """An explicit gather combine on the TPU fails at plan resolution with
    a message naming onehot: never deep in Mosaic, never rewritten."""
    import jax

    rt = get_runtime("pallas_step", combine="gather")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="onehot"):
        rt.build(graph(pattern))
    monkeypatch.undo()
    # off the TPU the gather ablation still runs (interpret mode)
    np.testing.assert_allclose(rt.execute(graph(pattern)),
                               get_runtime("fused").execute(graph(pattern)),
                               rtol=1e-6, atol=1e-6)


BUTTERFLY = list(_patterns.BUTTERFLY_PATTERNS)


@pytest.mark.parametrize("pattern", BUTTERFLY)
@pytest.mark.parametrize("S", [1, 3, 8])
def test_pallas_step_butterfly_bit_identical_to_fused(pattern, S):
    """Acceptance: fft/tree run BIT-identical to the fused oracle at every
    S (stride plan per-step; blocked requests route through the gathered
    plan's time-varying per-depth tables). Power-of-two widths make every
    butterfly combine weight exactly 0.5, so 0.5*a + 0.5*b must equal the
    oracle's (a + b) / 2 to the last bit. T=7 with S=3 exercises the
    masked tail; S=8 clamps to one fully-masked-tail launch."""
    g = graph(pattern, steps=7)
    ref = get_runtime("fused").execute(g)
    out = get_runtime("pallas_step", steps_per_launch=S).execute(g)
    assert np.array_equal(out, ref), f"{pattern} S={S}: bits differ"


@pytest.mark.parametrize("pattern", ["spread", "all_to_all"])
@pytest.mark.parametrize("S", [1, 4])
def test_pallas_step_global_patterns_match_fused(pattern, S):
    """The all-gather plan (spread's in-scan rotation, all_to_all's static
    global tables) matches fused at S in {1, 4}."""
    g = graph(pattern, steps=7)
    ref = get_runtime("fused").execute(g)
    out = get_runtime("pallas_step", steps_per_launch=S).execute(g)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                               err_msg=f"{pattern} S={S}")


@pytest.mark.parametrize("combine", ["window", "gather", "onehot"])
@pytest.mark.parametrize("pattern", ["fft", "spread"])
def test_pallas_step_nonhalo_combine_modes(pattern, combine):
    """Non-halo plans accept every combine option ("window" maps to the
    onehot lowering) in both the per-step and blocked schedules."""
    g = graph(pattern, steps=6)
    ref = get_runtime("fused").execute(g)
    for S in (1, 3):
        out = get_runtime("pallas_step", combine=combine,
                          steps_per_launch=S).execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{pattern} {combine} S={S}")


def test_pallas_step_butterfly_dispatch_accounting():
    """Launch accounting mirrors the executed plan exactly: the stride
    plan is per-step BY CONSTRUCTION, so a butterfly run only drops below
    T launches when the blocked request actually re-routes through the
    all-gather plan (width under the cap)."""
    g = graph("fft", steps=7)  # W=16
    assert get_runtime("pallas_step").dispatches_per_run(g) == 7
    # blocked request -> gathered plan: 1 + ceil(6/3) launches
    assert get_runtime(
        "pallas_step", steps_per_launch=3).dispatches_per_run(g) == 3
    # width over the cap: per-step stride plan regardless of the request
    assert get_runtime("pallas_step", steps_per_launch=3,
                       gather_width_cap=8).dispatches_per_run(g) == 7
    # "auto" KEEPS the stride plan (the gathered pays-off model ranks
    # blocked gathers against per-step gathers, not against the cheaper
    # stride plan it would displace) — only an explicit depth re-routes
    auto = get_runtime("pallas_step", steps_per_launch="auto")
    assert auto.dispatches_per_run(g) == g.steps
    ref = get_runtime("fused").execute(g)
    assert np.array_equal(auto.execute(g), ref)


def test_pallas_step_gather_transports_bit_identical():
    """Both stride/gather transports (fused all-gather vs per-collective
    ppermute) move exact row copies; outputs must not differ by a bit."""
    for pattern in ("fft", "spread"):
        g = graph(pattern, steps=6)
        a = get_runtime("pallas_step").execute(g)
        b = get_runtime("pallas_step", halo_impl="ppermute").execute(g)
        assert np.array_equal(a, b), pattern


# ------------------- pallas_step stride plan, one timestep at a time

def stride_step_mismatches(width, payload, slots, *, tile=None, steps=1000):
    """The stride plan's step (``_stride_step_fns``, one device) at each
    timestep t of ``slots``, on a fresh planted state, against one
    independent step in numpy: the mean of rows p and
    p XOR 2^((t-1) mod log2 W), then the 4-iteration compute_bound body.
    Returns {t: elements that differ bit for bit}; NaN matches NaN. A
    1000-step output cannot show these partners: after one period every
    column is constant across the rows."""
    g = TaskGraph(steps=steps, width=width, payload=payload, pattern="fft",
                  kernel=KernelSpec("compute_bound", 4))
    rt = get_runtime("pallas_step", devices=jax.devices()[:1])
    _, step = rt._stride_step_fns(g, tile=tile)
    run = jax.jit(lambda x, t: step(x, (), t))
    levels = int(np.log2(width))
    rows = np.arange(width)
    bad = {}
    for t in slots:
        x = _planted_init(width, payload, t)
        got = np.asarray(run(jnp.asarray(x), jnp.int32(t)))
        with np.errstate(invalid="ignore"):
            y = (x + x[rows ^ (1 << ((t - 1) % levels))]) / np.float32(2)
            for _ in range(4):
                y = np.float32(0.5) * y + np.float32(0.1)
        same = (got == y) | (np.isnan(got) & np.isnan(y))
        bad[t] = int((~same).sum())
    return bad


@pytest.mark.parametrize("tile", [None, CHIP_TILE], ids=["interp", "chip"])
def test_pallas_step_stride_step_partners_bit_identical(tile):
    """fft at W=64 (L=6): every slot of two periods and the wrap (t = 1 to
    13) and the last step of a 1000-step graph (t = 999), bit for bit."""
    bad = stride_step_mismatches(64, 64, list(range(1, 14)) + [999],
                                 tile=tile)
    assert not any(bad.values()), bad


STRIDE_LOOP_CASES = [("fft", 64), ("tree", 16)]  # periods 6 and 8


@pytest.mark.parametrize("tile", [None, CHIP_TILE], ids=["interp", "chip"])
@pytest.mark.parametrize("pattern,width", STRIDE_LOOP_CASES,
                         ids=[f"{p}-w{w}" for p, w in STRIDE_LOOP_CASES])
def test_pallas_step_stride_loop_bit_identical_every_remainder(
        pattern, width, tile):
    """The single-graph stride program (q straight-line periods in the
    scan, then the remainder's r levels) against ``fused``, bit for bit,
    at every T from 1 to 2P+2: the empty scan (T-1 < P), every remainder
    (T-1) mod P, and the wrap into a second and third period."""
    rt = get_runtime("pallas_step", devices=jax.devices()[:1])
    fused = get_runtime("fused")
    period = graph(pattern, width=width).period
    for T in range(1, 2 * period + 3):
        g = TaskGraph(steps=T, width=width, payload=8, pattern=pattern,
                      kernel=KernelSpec("compute_bound", 2))
        assert rt.plan_for(g)[0] == "stride"
        x = jnp.asarray(_planted_init(width, 8, T))
        out = np.asarray(rt._build_plan_stepper(g, "stride", tile=tile)(x))
        np.testing.assert_array_equal(out, fused.execute(g, x),
                                      err_msg=f"{pattern} T={T} {tile}")


@pytest.fixture
def non_contracting_body(monkeypatch):
    """The FMA body with A = B = 1: each iteration adds 1, so no state
    contracts and a dropped or repeated step shows in every element. The
    constants are read at trace time, so the jit caches are cleared on
    both sides of the patch."""
    from repro.kernels import bodies

    jax.clear_caches()
    monkeypatch.setattr(bodies, "FMA_A", 1.0)
    monkeypatch.setattr(bodies, "FMA_B", 1.0)
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("steps,remainder", [(1000, 3), (997, 0)])
def test_pallas_step_stride_loop_step_count(non_contracting_body, steps,
                                            remainder):
    """fft at W 64 (P 6), grain 1, under a body that adds 1 a step: the
    built program's output equals ``fused`` and an independent numpy
    loop exactly, so it ran every one of the T steps; the
    ``pallas_step.program`` span reports the q scan trips and the r
    remainder levels it ran them as."""
    W, payload, levels = 64, 8, 6
    g = TaskGraph(steps=steps, width=W, payload=payload, pattern="fft",
                  kernel=KernelSpec("compute_bound", 1))
    x = np.random.default_rng(steps).random((W, payload), dtype=np.float32)
    rt = get_runtime("pallas_step", devices=jax.devices()[:1], trace=True)
    out = np.asarray(rt.build(g)(jnp.asarray(x)))
    attrs = {sp.name: sp for sp in rt.tracer.spans}[
        "pallas_step.program"].attrs
    assert (attrs["period"], attrs["period_trips"],
            attrs["remainder_levels"]) == (levels, 166, remainder)
    rows = np.arange(W)
    y = x + np.float32(1)  # t=0: the body alone
    for t in range(1, steps):
        y = (y + y[rows ^ (1 << ((t - 1) % levels))]) / np.float32(2)
        y = y + np.float32(1)
    np.testing.assert_array_equal(out, y)
    np.testing.assert_array_equal(
        out, get_runtime("fused").execute(g, jnp.asarray(x)))


def test_pallas_step_stride_scopes_reach_op_metadata():
    """The stride plan's per-step ops carry the named scopes ``xor_swap``
    (the in-block swap) and ``pair_src`` (the [x | partner] stack) into
    the lowered module, in every branch of the step's switch and at t=0."""
    g = graph("fft", steps=6)  # W=16: four levels, four branches
    t0, step = get_runtime("pallas_step")._stride_step_fns(g)
    text = jax.jit(lambda x, t: step(t0(x, ()), (), t)).lower(
        jnp.zeros((16, 8), jnp.float32), jnp.int32(2)).as_text(
            debug_info=True)
    names = re.findall(r'loc\("([^"]+)"', text)
    for b in range(4):
        for scope in ("xor_swap", "pair_src"):
            assert any(f"branch_{b}_fun/{scope}/" in n for n in names), (
                b, scope)
    assert any(n.startswith("jit(<lambda>)/pair_src/") for n in names)


def test_pallas_step_mixed_plan_ensemble():
    """A tuple ensemble mixing all three plans (halo stencil, stride fft,
    allgather spread) with heterogeneous steps: one jitted scan, shared
    per-step cadence, every member matches running alone under fused."""
    base = dict(width=16, payload=8)
    members = [
        TaskGraph(steps=6, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), seed=0, **base),
        TaskGraph(steps=4, pattern="fft",
                  kernel=KernelSpec("compute_bound", 4), seed=1, **base),
        TaskGraph(steps=7, pattern="spread", fanout=3,
                  kernel=KernelSpec("compute_bound", 16), seed=2, **base),
        TaskGraph(steps=2, pattern="all_to_all",
                  kernel=KernelSpec("compute_bound", 8), seed=3, **base),
    ]
    ens = GraphEnsemble(members)
    for S in (1, 4):  # non-halo members pin the shared cadence to per-step
        rt = get_runtime("pallas_step", steps_per_launch=S)
        outs = rt.execute_ensemble(ens)
        for k, (g, out) in enumerate(zip(members, outs)):
            ref = get_runtime("fused").execute(g)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                       err_msg=f"S={S} member {k}")
        # per-step cadence -> every member launches every lockstep step
        assert rt.ensemble_dispatches_per_run(ens) == len(members) * ens.steps


def test_measure_returns_sane_sample():
    g = graph("stencil_1d", steps=4, kernel=KernelSpec("compute_bound", 32))
    rt = get_runtime("fused")
    sample, stats = rt.measure(g, reps=2, warmup=1)
    assert sample.wall_time > 0
    assert sample.total_flops == g.total_flops()
    assert stats.best <= stats.mean
    assert len(stats.walls) == 2


def test_unsupported_graph_raises():
    g = graph("fft")  # butterfly on 1 device is fine; force failure via width
    rt = get_runtime("bsp")
    bad = graph("stencil_1d", width=15)  # not divisible by devices=1? is ok
    # width 15 on 1 device divides; use radius > block instead
    g2 = TaskGraph(steps=3, width=4, pattern="nearest", radius=5,
                   kernel=KernelSpec("empty"))
    ok, why = rt.supports(g2)
    assert not ok and "radius" in why
    with pytest.raises(ValueError):
        rt.execute(g2)


# ---------------------------------------------------------- graph ensembles


def mixed_ensemble(**kw):
    """Mixed patterns, grains, and seeds; stackable (uniform width/payload)."""
    base = dict(steps=6, width=16, payload=8, seed=0)
    base.update(kw)
    return GraphEnsemble([
        TaskGraph(pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), **base),
        TaskGraph(pattern="nearest", radius=2,
                  kernel=KernelSpec("compute_bound", 32),
                  **{**base, "seed": base["seed"] + 1}),
        TaskGraph(pattern="fft",
                  kernel=KernelSpec("compute_bound", 4),
                  **{**base, "seed": base["seed"] + 2}),
    ])


@pytest.mark.parametrize("backend", ["fused", "serialized", "bsp",
                                     "bsp_scan", "overlap", "pallas_step"])
def test_ensemble_members_match_fused(backend):
    """Core invariant, ensemble edition: every backend's concurrent run must
    reproduce, per member, the state of running that member alone."""
    ens = mixed_ensemble()
    rt = get_runtime(backend)
    ok, why = rt.supports_ensemble(ens)
    if not ok:  # overlap refuses fft — swap in a halo-only ensemble for it
        ens = GraphEnsemble([g for g in ens
                             if rt.supports(g)[0]])
        assert len(ens) >= 2, why
    outs = rt.execute_ensemble(ens)
    for k, (g, out) in enumerate(zip(ens.members, outs)):
        ref = get_runtime("fused").execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{backend} member {k}")


def test_ensemble_heterogeneous_shapes():
    """Non-stackable members (different width/payload) run via the
    tuple-carry fallback and still match per-member fused."""
    ens = GraphEnsemble([
        TaskGraph(steps=5, width=16, payload=8, pattern="stencil_1d", seed=1),
        TaskGraph(steps=5, width=8, payload=4, pattern="all_to_all", seed=2),
        TaskGraph(steps=5, width=32, payload=8, pattern="spread", fanout=3,
                  seed=3),
    ])
    assert not ens.stackable
    for backend in ("fused", "serialized", "bsp", "bsp_scan"):
        outs = get_runtime(backend).execute_ensemble(ens)
        for g, out in zip(ens.members, outs):
            ref = get_runtime("fused").execute(g)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                       err_msg=backend)


def test_ensemble_validation():
    g = TaskGraph(steps=4, width=8)
    with pytest.raises(ValueError):
        GraphEnsemble([])
    with pytest.raises(ValueError):
        GraphEnsemble([g, TaskGraph(steps=4, width=4)]).dependency_arrays()


def test_ensemble_heterogeneous_steps_metadata():
    """Mismatched steps are allowed: lockstep T = max, members report own."""
    ens = GraphEnsemble([TaskGraph(steps=4, width=8),
                         TaskGraph(steps=7, width=8),
                         TaskGraph(steps=1, width=8)])
    assert ens.steps == 7
    assert ens.member_steps == (4, 7, 1)
    assert ens.heterogeneous_steps
    assert ens.num_tasks == (4 + 7 + 1) * 8
    assert not GraphEnsemble([TaskGraph(steps=4, width=8)]).heterogeneous_steps


@pytest.mark.parametrize("backend", ["fused", "serialized", "bsp",
                                     "bsp_scan", "overlap", "pallas_step"])
def test_ensemble_heterogeneous_steps_match_fused(backend):
    """Masked freezing: a member whose T is exhausted carries its final
    state unchanged, so member k of the lockstep run == running member k
    alone (its own T) under fused — for EVERY backend."""
    base = dict(width=16, payload=8)
    members = [
        TaskGraph(steps=3, pattern="stencil_1d",
                  kernel=KernelSpec("compute_bound", 8), seed=0, **base),
        TaskGraph(steps=6, pattern="nearest", radius=2,
                  kernel=KernelSpec("compute_bound", 32), seed=1, **base),
        TaskGraph(steps=4, pattern="fft",
                  kernel=KernelSpec("compute_bound", 4), seed=2, **base),
        TaskGraph(steps=1, pattern="dom",
                  kernel=KernelSpec("compute_bound", 8), seed=3, **base),
    ]
    ens = GraphEnsemble(members)
    rt = get_runtime(backend)
    ok, why = rt.supports_ensemble(ens)
    if not ok:  # overlap/pallas_step refuse fft — drop unsupported members
        ens = GraphEnsemble([g for g in members if rt.supports(g)[0]])
        assert len(ens) >= 3, why
        assert ens.heterogeneous_steps
    outs = rt.execute_ensemble(ens)
    for k, (g, out) in enumerate(zip(ens.members, outs)):
        ref = get_runtime("fused").execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{backend} member {k} T={g.steps}")


def test_ensemble_heterogeneous_steps_nonstackable():
    """Freezing also holds on the ragged-shape (tuple-carry) paths."""
    members = [
        TaskGraph(steps=5, width=16, payload=8, pattern="stencil_1d", seed=1),
        TaskGraph(steps=2, width=8, payload=4, pattern="all_to_all", seed=2),
        TaskGraph(steps=7, width=32, payload=8, pattern="spread", fanout=3,
                  seed=3),
    ]
    ens = GraphEnsemble(members)
    assert not ens.stackable and ens.heterogeneous_steps
    for backend in ("fused", "serialized", "bsp", "bsp_scan"):
        outs = get_runtime(backend).execute_ensemble(ens)
        for g, out in zip(members, outs):
            ref = get_runtime("fused").execute(g)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                       err_msg=backend)


def test_ensemble_heterogeneous_steps_dispatch_accounting():
    """Frozen members must not be charged dispatches past their own T."""
    ens = GraphEnsemble([TaskGraph(steps=3, width=8),
                         TaskGraph(steps=7, width=8)])
    assert get_runtime("bsp").ensemble_dispatches_per_run(ens) == 3 + 7
    assert (get_runtime("serialized").ensemble_dispatches_per_run(ens)
            == (3 + 7) * 8)
    # stacked ensemble: ALL members share each launch -> lockstep launches
    # (1 body launch + ceil((Tmax-1)/S) combine launches), not 1; the
    # pipelined default splits each combine launch into boundary + interior
    assert get_runtime("pallas_step").ensemble_dispatches_per_run(ens) == 7
    assert get_runtime(
        "pallas_step", steps_per_launch=3,
        pipeline=False).ensemble_dispatches_per_run(ens) == 3
    assert get_runtime(
        "pallas_step", steps_per_launch=3).ensemble_dispatches_per_run(ens) == 5
    # mixed-spec (tuple) fallback launches each member every scan iteration
    mixed = GraphEnsemble([
        TaskGraph(steps=3, width=8),
        TaskGraph(steps=7, width=8, kernel=KernelSpec("compute_bound", 99)),
    ])
    assert get_runtime("pallas_step").ensemble_dispatches_per_run(mixed) == 14
    assert get_runtime(
        "pallas_step", steps_per_launch=3, pipeline=False
    ).ensemble_dispatches_per_run(mixed) == 6
    assert get_runtime(
        "pallas_step", steps_per_launch=3
    ).ensemble_dispatches_per_run(mixed) == 10


def test_ensemble_padded_dependency_arrays():
    ens = mixed_ensemble()
    idx, mask, periods = ens.dependency_arrays()
    K, Pmax, W, Dmax = idx.shape
    assert K == 3 and W == 16
    assert Pmax == max(g.period for g in ens.members)
    assert Dmax == max(g.max_deps for g in ens.members)
    assert list(periods) == [g.period for g in ens.members]
    # padded slices must reproduce each member's own arrays exactly
    for k, g in enumerate(ens.members):
        gi, gm = g.dependency_arrays()
        D = gi.shape[2]
        for s in range(Pmax):
            np.testing.assert_array_equal(idx[k, s, :, :D], gi[s % g.period])
            np.testing.assert_array_equal(mask[k, s, :, :D], gm[s % g.period])
            assert (mask[k, s, :, D:] == 0).all()


def test_ensemble_dispatch_accounting():
    ens = mixed_ensemble(steps=7)
    per_member_tasks = sum(g.num_tasks for g in ens.members)
    assert get_runtime("fused").ensemble_dispatches_per_run(ens) == 1
    assert get_runtime("bsp_scan").ensemble_dispatches_per_run(ens) == 1
    assert get_runtime("bsp").ensemble_dispatches_per_run(ens) == 7 * 3
    assert (get_runtime("serialized").ensemble_dispatches_per_run(ens)
            == per_member_tasks)


def test_ensemble_single_member_matches_single_graph():
    g = graph("stencil_1d")
    ens = GraphEnsemble([g])
    for backend in available_runtimes():
        out = get_runtime(backend).execute_ensemble(ens)[0]
        ref = get_runtime(backend).execute(g)
        np.testing.assert_allclose(out, ref, rtol=1e-6, err_msg=backend)


def test_measure_ensemble_aggregates():
    ens = mixed_ensemble(steps=4)
    sample, stats = get_runtime("fused").measure_ensemble(ens, reps=2,
                                                          warmup=1)
    assert sample.num_tasks == sum(g.num_tasks for g in ens.members)
    assert sample.total_flops == pytest.approx(
        sum(g.total_flops() for g in ens.members))
    assert sample.wall_time == stats.best > 0
    assert len(stats.walls) == 2


# ------------------------------------------------- combine primitive units


def test_combine_dependencies_mean_semantics():
    import jax.numpy as jnp

    outputs = jnp.arange(4, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
    idx = jnp.array([[0, 1, 0], [2, 3, 0], [0, 0, 0], [1, 1, 1]], jnp.int32)
    mask = jnp.array([[1, 1, 0], [1, 1, 0], [1, 0, 0], [1, 1, 1]],
                     jnp.float32)
    got = combine_dependencies(outputs, idx, mask)
    np.testing.assert_allclose(np.asarray(got[0]), 0.5 * np.ones(4))
    np.testing.assert_allclose(np.asarray(got[1]), 2.5 * np.ones(4))
    np.testing.assert_allclose(np.asarray(got[2]), 0.0 * np.ones(4))
    np.testing.assert_allclose(np.asarray(got[3]), 1.0 * np.ones(4))


def test_combine_zero_deps_keeps_own_state():
    import jax.numpy as jnp

    outputs = jnp.arange(4, dtype=jnp.float32)[:, None] * jnp.ones((1, 2))
    idx = jnp.zeros((4, 1), jnp.int32)
    mask = jnp.zeros((4, 1), jnp.float32)
    got = combine_dependencies(outputs, idx, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(outputs))


def test_combine_all_to_all_is_global_mean():
    import jax.numpy as jnp

    outputs = jnp.arange(8, dtype=jnp.float32)[:, None] * jnp.ones((1, 3))
    got = np.asarray(combine_all_to_all(outputs))
    np.testing.assert_allclose(got, 3.5 * np.ones((8, 3)))
