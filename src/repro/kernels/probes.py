"""Measured cost model: probe microbenchmarks behind the scheduling policy.

``schedule.py`` ranks launch depths and plans with covers/pays-off rules
expressed in *row-steps* against one exchange-cost constant
(``PIPELINE_EXCHANGE_ROW_STEPS``). That constant is a hand calibration of
THIS container; the paper's whole point is that such constants are
measurable per platform and that cross-system verdicts only hold when they
are re-measured. This module does the measuring:

  probe_launch_us          per-launch dispatch cost (tiny step kernel)
  probe_row_step_us        marginal cost of one working row advanced one
                           depth (slope of the step kernel over width)
  probe_halo_exchange_us   one deep ring exchange, per HALO_ASYNC_IMPLS key
  probe_stride_exchange_us one XOR block exchange, per STRIDE_ASYNC_IMPLS
                           key (power-of-two device counts only)
  probe_gather_us          ``gather_global`` wall as a function of width

``run_probes`` bundles the results into a :class:`CostModel` and
``save_cost_model`` persists it under ``artifacts/bench/cost_model.json``,
keyed per (platform, device count, payload) so one cache file serves many
configurations. ``default_cost_model`` is the resolution every scheduling
decision goes through when no model is passed explicitly; precedence:

  explicit option  a CostModel handed to the resolver / runtime wins
  env              REPRO_PIPELINE_EXCHANGE_ROW_STEPS overrides the
                   exchange constant (source="env"; the PR-5 calibration
                   knob keeps working, and keeps beating cached probes so
                   a one-off experiment never has to delete the cache)
  cached probes    a matching entry in the cache file (REPRO_COST_MODEL
                   names the file; unset -> the default path; "off"
                   disables the cache entirely, which is what the test
                   suite pins so ambient calibrations cannot flip
                   analytic-expectation tests)
  analytic         the documented fallback: PIPELINE_EXCHANGE_ROW_STEPS,
                   no measured launch/gather costs, plans not rankable

Only a *measured* model can rank the STRIDE vs ALLGATHER plan choice
(``schedule.gathered_beats_strides``): the analytic model knows one ratio
(exchange/row-step), but plan ranking needs the absolute launch, gather
and stride walls, which no single constant encodes.

CLI (also the CI calibration step and the benchmarks' ``--calibrate``
subprocess target)::

    python -m repro.kernels.probes --smoke --devices 2 \
        --out artifacts/bench/cost_model.json

Heavy imports (jax, the transports) happen inside the probe functions, so
importing this module — which schedule.py does lazily on every default
resolution — costs nothing beyond the stdlib.
"""
from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple, Union

from repro.kernels import schedule as _schedule

#: Cache layout version; bump on any incompatible CostModel field change.
#: Loads fail LOUDLY on mismatch — a silently reinterpreted calibration is
#: worse than a crash (same philosophy as the env-var parse).
SCHEMA_VERSION = 1

#: REPRO_COST_MODEL: path of the calibration cache file; empty/unset ->
#: the default path below; one of _DISABLE_VALUES -> no cache (analytic
#: fallback unless the env constant is set).
COST_MODEL_ENV = "REPRO_COST_MODEL"

_DISABLE_VALUES = ("off", "0", "none", "disabled")

#: repo-root anchored, matching benchmarks.common.bench_path("cost_model.json")
DEFAULT_CACHE_PATH = (
    Path(__file__).resolve().parents[3] / "artifacts" / "bench"
    / "cost_model.json"
)

_AXIS = "shard"  # the bsp mesh axis name (repro.core.runtimes.bsp.AXIS)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """The costs the scheduling policy runs on, and where they came from.

    ``exchange_row_steps`` is the one number every covers/pays-off rule
    consumes (schedule.py's X); the remaining fields exist only on
    measured models and enable plan *ranking* on top of depth choice.
    All wall costs are microseconds.
    """

    source: str  # "analytic" | "env" | "measured"
    exchange_row_steps: float
    launch_us: Optional[float] = None
    row_step_us: Optional[float] = None
    halo_exchange_us: Dict[str, float] = dataclasses.field(default_factory=dict)
    stride_exchange_us: Dict[str, float] = dataclasses.field(default_factory=dict)
    gather_us: Dict[int, float] = dataclasses.field(default_factory=dict)
    #: impl -> devices -> width -> us: the devices-dimension gather probes
    #: behind schedule.choose_gather_impl (chunked-vs-monolithic is a
    #: function of D, not just W, so the flat gather_us curve cannot rank
    #: transports). Optional — absent on pre-PR-9 calibrations, which
    #: still load (same schema) and simply fall back to the structural
    #: gather rule.
    gather_impl_us: Dict[str, Dict[int, Dict[int, float]]] = (
        dataclasses.field(default_factory=dict))
    platform: str = ""
    devices: int = 0
    payload: int = 0

    # ------------------------------------------------------------ queries

    @property
    def is_measured(self) -> bool:
        return self.source == "measured"

    @property
    def can_rank_plans(self) -> bool:
        """Plan ranking needs absolute costs: launch, row-step and at
        least one measured gather width. (Stride cost is only needed when
        the graph actually has off-block strides; ``stride_us_for``
        returning None makes that case unrankable at the call site.)"""
        return (self.is_measured and self.launch_us is not None
                and self.row_step_us is not None and bool(self.gather_us))

    @staticmethod
    def _interp_width(curve: Dict[int, float],
                      width: int) -> Optional[float]:
        """Piecewise-linear over probed widths, clamp-extrapolated with
        the end slopes (collective walls are near-affine in bytes moved
        at these sizes). None on an empty curve."""
        if not curve:
            return None
        pts = sorted(curve.items())
        if len(pts) == 1 or width <= pts[0][0]:
            lo, hi = pts[0], pts[min(1, len(pts) - 1)]
        elif width >= pts[-1][0]:
            lo, hi = pts[-2], pts[-1]
        else:
            lo = max(p for p in pts if p[0] <= width)
            hi = min(p for p in pts if p[0] >= width)
        if lo[0] == hi[0]:
            return float(lo[1])
        slope = (hi[1] - lo[1]) / (hi[0] - lo[0])
        return float(max(0.0, lo[1] + slope * (width - lo[0])))

    def gather_us_at(self, width: int) -> Optional[float]:
        """Measured ``gather_global`` wall at ``width`` (default
        transport), interpolated per :meth:`_interp_width`. None when the
        model has no gather probes."""
        return self._interp_width(self.gather_us, width)

    def gather_walls_at(self, width: int,
                        devices: Optional[int] = None) -> Dict[str, float]:
        """Per-transport gather walls at (devices, width) from the
        devices-dimension probes: impl -> interpolated us, only for impls
        probed at exactly ``devices`` (a wall measured at D' devices says
        nothing about the rendezvous structure at D — the same
        exact-device-match rule ``_match_entry`` enforces for whole
        models). Empty when nothing was probed at that count."""
        d = int(devices) if devices is not None else self.devices
        out: Dict[str, float] = {}
        for impl, by_devices in self.gather_impl_us.items():
            us = self._interp_width(by_devices.get(d, {}), width)
            if us is not None:
                out[impl] = us
        return out

    def stride_us_for(self, impl: str = "xla") -> Optional[float]:
        """One XOR block-exchange wall for ``impl``, falling back to any
        probed transport (the relative plan verdict rarely hinges on the
        transport; missing entirely -> None, caller treats as unrankable)."""
        if impl in self.stride_exchange_us:
            return float(self.stride_exchange_us[impl])
        if self.stride_exchange_us:
            return float(min(self.stride_exchange_us.values()))
        return None

    def describe(self, width: Optional[int] = None) -> str:
        """The verdict source, for supports()/tuner-decline messages —
        a wrong auto-pick must be diagnosable from the error alone."""
        if self.source == "env":
            return (f"env override {_schedule._EXCHANGE_ROW_STEPS_ENV}="
                    f"{self.exchange_row_steps:g} row-steps")
        if not self.is_measured:
            return (f"analytic fallback "
                    f"(exchange={self.exchange_row_steps:g} row-steps)")
        parts = [f"measured on {self.platform} x{self.devices}"]
        costs = []
        if self.halo_exchange_us:
            costs.append(f"exchange={min(self.halo_exchange_us.values()):.1f}us")
        stride = self.stride_us_for()
        if stride is not None:
            costs.append(f"stride={stride:.1f}us")
        g = self.gather_us_at(width) if width else None
        if g is not None:
            costs.append(f"gather={g:.1f}us@w{width}")
        elif self.gather_us:
            w, us = sorted(self.gather_us.items())[-1]
            costs.append(f"gather={us:.1f}us@w{w}")
        if self.launch_us is not None:
            costs.append(f"launch={self.launch_us:.1f}us")
        if self.row_step_us is not None:
            costs.append(f"row-step={self.row_step_us:.3f}us")
        return (f"{parts[0]}: " + ", ".join(costs)
                + f" -> exchange={self.exchange_row_steps:g} row-steps")

    # -------------------------------------------------------------- codec

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # JSON object keys are strings; keep widths sorted for stable files
        d["gather_us"] = {str(k): v for k, v in sorted(self.gather_us.items())}
        d["gather_impl_us"] = {
            impl: {str(dd): {str(w): us for w, us in sorted(curve.items())}
                   for dd, curve in sorted(by_d.items())}
            for impl, by_d in sorted(self.gather_impl_us.items())}
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "CostModel":
        known = {f.name for f in dataclasses.fields(cls)}
        extra = set(d) - known
        if extra:
            raise ValueError(f"unknown CostModel fields {sorted(extra)}")
        d = dict(d)
        d["gather_us"] = {int(k): float(v)
                          for k, v in d.get("gather_us", {}).items()}
        d["gather_impl_us"] = {
            str(impl): {int(dd): {int(w): float(us)
                                  for w, us in curve.items()}
                        for dd, curve in by_d.items()}
            for impl, by_d in d.get("gather_impl_us", {}).items()}
        return cls(**d)

    def cache_key(self) -> str:
        return f"{self.platform}|d{self.devices}|p{self.payload}"


def analytic_cost_model() -> CostModel:
    """The documented fallback: schedule.py's hand-calibrated constant,
    no absolute costs, plans not rankable."""
    return CostModel(source="analytic",
                     exchange_row_steps=float(
                         _schedule.PIPELINE_EXCHANGE_ROW_STEPS))


def _env_cost_model(raw: str) -> CostModel:
    """REPRO_PIPELINE_EXCHANGE_ROW_STEPS as a model; invalid values fail
    loudly (same contract as schedule.exchange_row_steps always had)."""
    value = int(raw)
    if value <= 0:
        raise ValueError(
            f"{_schedule._EXCHANGE_ROW_STEPS_ENV} must be a positive "
            f"integer, got {raw!r}")
    return CostModel(source="env", exchange_row_steps=float(value))


# --------------------------------------------------------------- cache file


def save_cost_model(model: CostModel, path=None) -> Path:
    """Merge one calibration into the cache file (other keys survive)."""
    path = Path(path) if path is not None else DEFAULT_CACHE_PATH
    entries: Dict[str, CostModel] = {}
    if path.exists():
        entries = load_cost_model(path)
    entries[model.cache_key()] = model
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "schema": SCHEMA_VERSION,
        "entries": {k: m.to_dict() for k, m in sorted(entries.items())},
    }
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return path


def load_cost_model(path=None) -> Dict[str, CostModel]:
    """All cached calibrations, keyed "platform|dD|pP". Corrupt files and
    schema mismatches raise ValueError."""
    path = Path(path) if path is not None else DEFAULT_CACHE_PATH
    try:
        raw = json.loads(path.read_text())
    except json.JSONDecodeError as e:
        raise ValueError(f"corrupt cost-model cache {path}: {e}") from None
    if not isinstance(raw, dict) or raw.get("schema") != SCHEMA_VERSION:
        raise ValueError(
            f"cost-model cache {path} has schema {raw.get('schema')!r}, "
            f"this build reads schema {SCHEMA_VERSION} — re-run "
            f"`python -m repro.kernels.probes` to recalibrate")
    try:
        return {k: CostModel.from_dict(v)
                for k, v in raw.get("entries", {}).items()}
    except (TypeError, ValueError) as e:
        raise ValueError(f"corrupt cost-model cache {path}: {e}") from None


def _match_entry(entries: Dict[str, CostModel], platform: str,
                 devices: Optional[int],
                 payload: Optional[int]) -> Optional[CostModel]:
    """Best cached calibration for the current context: platform must
    match exactly; device count must match when known (scheduling
    verdicts at D devices judged by a D'-device calibration would be
    exactly the cross-platform mistake this module exists to kill);
    payload picks the nearest probe (costs vary slowly in payload — the
    lane padding quantizes it anyway)."""
    pool = [m for m in entries.values() if m.platform == platform]
    if devices is not None:
        pool = [m for m in pool if m.devices == devices]
    if not pool:
        return None
    if payload is not None:
        pool.sort(key=lambda m: (abs(m.payload - payload), m.payload))
    else:
        pool.sort(key=lambda m: m.payload)
    return pool[0]


def _platform() -> str:
    import jax

    return jax.default_backend()


_default_cache: Dict[tuple, CostModel] = {}


def default_cost_model(devices: Optional[int] = None,
                       payload: Optional[int] = None) -> CostModel:
    """The model scheduling decisions use when none is passed explicitly.

    Precedence (locked by tests/test_cost_model.py):
    env constant > cached probes > analytic fallback. The explicit-option
    tier above these lives at the call sites (a ``model=`` argument or
    the runtime's ``cost_model`` option short-circuits this function
    entirely). Re-reads the environment per call — a harness can flip the
    env between resolutions without reimports — but memoizes file loads
    per (path, mtime), so hot resolver loops don't re-parse JSON."""
    raw_env = os.environ.get(_schedule._EXCHANGE_ROW_STEPS_ENV)
    if raw_env:
        return _env_cost_model(raw_env)
    raw_path = os.environ.get(COST_MODEL_ENV)
    if raw_path and raw_path.strip().lower() in _DISABLE_VALUES:
        return analytic_cost_model()
    path = Path(raw_path) if raw_path else DEFAULT_CACHE_PATH
    if not path.exists():
        return analytic_cost_model()
    mtime = path.stat().st_mtime_ns
    key = (str(path), mtime, _platform(), devices, payload)
    if key not in _default_cache:
        entry = _match_entry(load_cost_model(path), _platform(),
                             devices, payload)
        _default_cache[key] = entry if entry is not None \
            else analytic_cost_model()
    return _default_cache[key]


def coerce_cost_model(value, devices: Optional[int] = None,
                      payload: Optional[int] = None) -> CostModel:
    """A runtime's ``cost_model`` option -> CostModel. Accepts a
    CostModel, a to_dict()-shaped dict, or a cache-file path; None means
    "no explicit choice" and falls through to ``default_cost_model``."""
    if value is None:
        return default_cost_model(devices=devices, payload=payload)
    if isinstance(value, CostModel):
        return value
    if isinstance(value, dict):
        return CostModel.from_dict(value)
    if isinstance(value, (str, os.PathLike)):
        entry = _match_entry(load_cost_model(Path(value)), _platform(),
                             devices, payload)
        if entry is None:
            raise ValueError(
                f"cost-model file {value} has no entry for platform "
                f"{_platform()!r} at {devices} devices")
        return entry
    raise TypeError(
        f"cost_model option must be a CostModel, dict, or path; "
        f"got {type(value).__name__}")


# ------------------------------------------------------------------- probes


def _time_best_us(fn, reps: int, warmup: int = 1) -> float:
    """Best-of-reps wall of ``fn()`` in microseconds (block_until_ready
    inside the timed region; best-of matches the runtimes' TimingStats)."""
    import time

    import jax

    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _time_median_us(fn, reps: int, warmup: int = 2) -> float:
    """Median-of-reps wall of ``fn()`` in microseconds.

    For per-dispatch collectives on an oversubscribed (forced-host) mesh
    the wall distribution is heavy-tailed by thread scheduling — a full
    D-participant barrier pays a convoy tax whenever the scheduler wakes
    its threads in an unlucky order. Best-of-reps erases exactly that
    tail, ranking transports by a best case no dispatch cadence ever
    pays repeatedly; the median is what a host-stepped launch loop pays
    per launch, so transport CHOICE probes use it."""
    import time

    import jax

    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn())
    walls = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t0)
    walls.sort()
    n = len(walls)
    mid = n // 2
    med = walls[mid] if n % 2 else 0.5 * (walls[mid - 1] + walls[mid])
    return med * 1e6


def _step_call(width: int, payload: int):
    """A zero-arg thunk running ONE single-step window-mode launch of the
    fused step kernel over ``width`` rows (radius-1 three-point stencil:
    the same kernel + combine the halo plan times, so the launch and
    row-step probes price what the scheduler actually schedules)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as _kops

    src = jnp.zeros((1, width + 2, payload), jnp.float32)
    idx = jnp.zeros((1, width, 1), jnp.int32)
    wgt = jnp.asarray(np.full((1, width, 3), 1.0 / 3.0, np.float32))
    kw = dict(kind="compute_bound", iterations=1, combine="window")
    return lambda: _kops.taskbench_step(src, idx, wgt, **kw)


def probe_launch_us(payload: int = 64, *, reps: int = 5) -> float:
    """Per-launch dispatch cost: a step launch over rows too few for the
    body to matter is ~all dispatch."""
    return _time_best_us(_step_call(8, payload), reps)


def probe_row_step_us(payload: int = 64, *,
                      widths: Sequence[int] = (64, 256, 512),
                      reps: int = 5) -> float:
    """Marginal cost of one working row advanced one depth: the
    least-squares slope of the single-step launch wall over ``widths``
    (the intercept absorbs the dispatch cost the launch probe measures;
    fitting >= 3 points keeps one noisy sample from flipping the sign).
    Floored well above zero — a zero/negative slope is measurement noise
    and would make the derived exchange ratio explode."""
    reps = max(reps, 3)  # the slope is a difference of near-equal walls
    ws = sorted(set(int(w) for w in widths))
    ts = [_time_best_us(_step_call(w, payload), reps) for w in ws]
    n = len(ws)
    mw, mt = sum(ws) / n, sum(ts) / n
    var = sum((w - mw) ** 2 for w in ws)
    slope = sum((w - mw) * (t - mt) for w, t in zip(ws, ts)) / var
    return max(1e-3, slope)


def _probe_mesh(devices: int):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    avail = jax.devices()
    if devices > len(avail):
        raise ValueError(
            f"probe wants {devices} devices, jax sees {len(avail)} "
            f"(set XLA_FLAGS=--xla_force_host_platform_device_count "
            f"before jax initializes, or run via the probes CLI)")
    return Mesh(np.array(avail[:devices]), (_AXIS,))


def _sharded_wall_us(local_fn, devices: int, rows_per_device: int,
                     payload: int, reps: int,
                     stat: str = "best",
                     replicated_out: bool = False) -> float:
    """Wall of one jitted shard_map'd ``local_fn(local) -> array`` over a
    (devices*rows, payload) f32 operand. ``stat`` picks the aggregation:
    "best" (floor probes) or "median" (transport-choice probes — see
    ``_time_median_us`` for why). ``replicated_out`` returns the local
    fn's result replicated (P(None)) instead of row-sharded — gather
    probes need it so the program's product IS the gathered buffer; a
    reduction-style consumption instead invites XLA to rewrite the
    gather+reduce into a cheaper collective and the probe stops
    measuring the transport it names."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _probe_mesh(devices)
    out_specs = P(None) if replicated_out else P(_AXIS)
    fn = jax.jit(shard_map(local_fn, mesh=mesh, check_vma=False,
                           in_specs=P(_AXIS), out_specs=out_specs))
    arr = jnp.zeros((devices * rows_per_device, payload), jnp.float32)
    timer = _time_median_us if stat == "median" else _time_best_us
    return timer(lambda: fn(arr), reps)


def probe_halo_exchange_us(devices: int, payload: int = 64, *,
                           depth: int = 8,
                           reps: int = 5) -> Dict[str, float]:
    """One deep ring exchange per HALO_ASYNC_IMPLS transport. Rendezvous
    dominates at these sizes, so one depth stands in for all."""
    from repro.core.runtimes import _halo

    out: Dict[str, float] = {}
    block = max(2 * depth, 16)
    for impl in sorted(_halo.HALO_ASYNC_IMPLS):
        def local(x, impl=impl):
            h = _halo.exchange_edges_start(
                x[:depth], x[-depth:], devices, _AXIS, impl=impl)
            hl, hr = h.join()
            # consume both landing buffers so the collective can't be DCE'd
            return x + 0.0 * (hl.sum() + hr.sum())

        out[impl] = _sharded_wall_us(local, devices, block, payload, reps)
    return out


def probe_stride_exchange_us(devices: int, payload: int = 64, *,
                             block: int = 32,
                             reps: int = 5) -> Dict[str, float]:
    """One XOR block exchange (stride 1) per STRIDE_ASYNC_IMPLS transport.
    Skipped (empty dict) on non-power-of-two device counts and on a
    single device, mirroring the transport's own contract."""
    from repro.core.runtimes import _halo

    if devices < 2 or devices & (devices - 1):
        return {}
    out: Dict[str, float] = {}
    for impl in sorted(_halo.STRIDE_ASYNC_IMPLS):
        def local(x, impl=impl):
            h = _halo.exchange_stride_start(x, (1,), devices, _AXIS,
                                            impl=impl)
            (partner,) = h.join()
            return x + 0.0 * partner.sum()

        out[impl] = _sharded_wall_us(local, devices, block, payload, reps)
    return out


def probe_gather_us(devices: int, payload: int = 64, *,
                    widths: Sequence[int] = (64, 256, 512),
                    reps: int = 5) -> Dict[int, float]:
    """``gather_global`` wall per width (the all-gather plan's collective).
    Widths not divisible by the device count are skipped — the plan never
    runs them either."""
    from repro.core.runtimes import _halo

    out: Dict[int, float] = {}
    for width in sorted(set(int(w) for w in widths)):
        if width < devices or width % devices:
            continue

        def local(x):
            g = _halo.gather_global(x, devices, _AXIS)
            return x + 0.0 * g.sum()

        out[width] = _sharded_wall_us(local, devices, width // devices,
                                      payload, reps)
    return out


def _gather_probe_device_counts(devices: int) -> Tuple[int, ...]:
    """The devices-dimension grid: the calibration count and its /2, /4
    subdivisions when they divide it (subgroup meshes over a prefix of the
    same device set), all >= 2. One calibration run then serves the
    scaling sweep's smaller Ds without extra subprocesses."""
    counts = []
    for d in (devices, devices // 2, devices // 4):
        if d >= 2 and devices % d == 0 and d not in counts:
            counts.append(d)
    return tuple(counts)


def _chunk_group_candidates(devices: int) -> Tuple[int, ...]:
    """Proper divisors 1 < g < D — every grouping the chunked gather can
    actually run without degrading to the monolithic path."""
    return tuple(g for g in range(2, devices)
                 if devices % g == 0)


def probe_gather_impl_us(devices: int, payload: int = 64, *,
                         widths: Sequence[int] = (64, 256, 512),
                         impls: Sequence[str] = ("xla", "chunked"),
                         device_counts: Optional[Sequence[int]] = None,
                         reps: int = 25,
                         chunk_groups: Union[str, Sequence[int], None]
                         = "auto",
                         ) -> Dict[str, Dict[int, Dict[int, float]]]:
    """``gather_global`` wall per (transport, device count, width) — the
    devices-dimension behind ``schedule.choose_gather_impl``. Each sub
    count runs on a mesh over a prefix of the available devices; widths
    that don't divide a count are skipped for it, and impls that degrade
    to the monolithic path at a count (chunked with no usable segment
    split) are skipped there too so the table never ranks an impl against
    itself.

    ``chunk_groups`` adds grouping-anatomy rows for the chunked
    transport: each candidate G probes as a pseudo-impl key
    ``"chunked:g{G}"`` (forced via ``gather_global(chunk_group=G)``), the
    input behind ``schedule.choose_gather_chunk_group``'s measured tier.
    "auto" probes every proper divisor 1 < G < d of each count; an
    explicit sequence probes its members where they divide d; None skips
    grouping rows entirely. The colon keeps these keys out of the
    impl-choice ranking (choose_gather_impl filters them) while fitting
    the existing ``gather_impl_us`` cache schema unchanged.

    Walls are MEDIAN-of-reps, unlike the floor probes' best-of: the full
    D-participant barrier's wall is heavy-tailed by scheduler convoy
    effects on an oversubscribed mesh, and a transport choice paid on
    every host-stepped dispatch should be ranked by the typical wall,
    not a best case that erases exactly the tail the chunked gather's
    bounded rendezvous width avoids."""
    from repro.core.runtimes import _halo

    counts = tuple(device_counts) if device_counts is not None \
        else _gather_probe_device_counts(devices)
    out: Dict[str, Dict[int, Dict[int, float]]] = {}
    for impl in impls:
        if impl not in _halo.GATHER_IMPLS:
            raise ValueError(
                f"unknown gather impl {impl!r}; known "
                f"{sorted(_halo.GATHER_IMPLS)}")

    def _measure(key, impl, d, width, group=None):
        def local(x, impl=impl, d=d, group=group):
            # the program's output IS the gathered (W, P) buffer
            # (replicated_out) — what the allgather plan feeds
            # the kernel; see _sharded_wall_us for why a
            # reduction-style consumption would measure the
            # wrong collective
            return _halo.gather_global(x, d, _AXIS, impl=impl,
                                       chunk_group=group)

        us = _sharded_wall_us(local, d, width // d, payload, reps,
                              stat="median", replicated_out=True)
        out.setdefault(key, {}).setdefault(d, {})[width] = us

    for d in counts:
        for impl in impls:
            if impl == "chunked":
                g = _halo.gather_chunk_group(d)
                if g <= 1 or g >= d:
                    continue  # degrades to xla at this count
            for width in sorted(set(int(w) for w in widths)):
                if width < d or width % d:
                    continue
                _measure(impl, impl, d, width)
        if chunk_groups is None or "chunked" not in impls:
            continue
        groups = _chunk_group_candidates(d) if chunk_groups == "auto" \
            else tuple(g for g in chunk_groups
                       if 1 < int(g) < d and d % int(g) == 0)
        if len(groups) < 2:
            continue  # a single viable grouping is nothing to rank
        for g in groups:
            for width in sorted(set(int(w) for w in widths)):
                if width < d or width % d:
                    continue
                _measure(f"chunked:g{int(g)}", "chunked", d, width,
                         group=int(g))
    return out


def run_probes(devices: Optional[int] = None, payload: int = 64, *,
               reps: int = 5, smoke: bool = False) -> CostModel:
    """All probes -> one measured CostModel (not yet persisted).

    ``smoke`` shrinks reps and the width grids so a CI step finishes in
    seconds; the schema and the derivation are identical to a full run.
    """
    import jax

    if devices is None:
        devices = len(jax.devices())
    if smoke:
        reps = min(reps, 3)
        row_widths, gather_widths = (64, 256, 512), (64, 128)
    else:
        row_widths, gather_widths = (64, 256, 512), (64, 256, 512)
    launch = probe_launch_us(payload, reps=reps)
    row_step = probe_row_step_us(payload, widths=row_widths, reps=reps)
    halo = probe_halo_exchange_us(devices, payload, reps=reps)
    stride = probe_stride_exchange_us(devices, payload, reps=reps)
    gather = probe_gather_us(devices, payload, widths=gather_widths,
                             reps=reps)
    # Devices-dimension transport table (choose_gather_impl's input, plus
    # the "chunked:g{G}" grouping-anatomy rows choose_gather_chunk_group
    # ranks): smoke probes only the calibration count, full runs add the
    # /2, /4 subgroup counts so one calibration serves the scaling sweep.
    impl_counts = (devices,) if smoke else None
    # median-of-reps needs a real sample; don't let the floor probes'
    # small reps starve the transport-choice distribution
    impl_reps = max(reps, 5 if smoke else 25)
    gather_impl = probe_gather_impl_us(
        devices, payload, widths=gather_widths,
        device_counts=impl_counts, reps=impl_reps) if devices >= 2 else {}
    # The covers/pays-off unit: one exchange in row-steps, priced with the
    # DEFAULT transport ("xla") because that is what the pipelined
    # schedule runs unless ablated.
    exch = halo.get("xla", min(halo.values()) if halo else None)
    x = (exch / row_step) if exch else float(
        _schedule.PIPELINE_EXCHANGE_ROW_STEPS)
    return CostModel(
        source="measured",
        exchange_row_steps=float(max(1.0, x)),
        launch_us=float(launch),
        row_step_us=float(row_step),
        halo_exchange_us={k: float(v) for k, v in halo.items()},
        stride_exchange_us={k: float(v) for k, v in stride.items()},
        gather_us={k: float(v) for k, v in gather.items()},
        gather_impl_us={impl: {d: {w: float(us) for w, us in curve.items()}
                               for d, curve in by_d.items()}
                        for impl, by_d in gather_impl.items()},
        platform=_platform(),
        devices=int(devices),
        payload=int(payload),
    )


# ---------------------------------------------------------------------- CLI


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Calibrate and persist. MUST run before jax initializes when
    ``--devices`` exceeds the physical count (the CLI sets the host-device
    forcing flag itself; as a library call that is the caller's problem —
    benchmarks run this module in a subprocess for exactly that reason)."""
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=0,
                    help="device count to calibrate for (0 = current)")
    ap.add_argument("--payload", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grids/reps (CI calibration step)")
    ap.add_argument("--out", default=str(DEFAULT_CACHE_PATH),
                    help="cache file to merge into ('-' = don't persist)")
    ap.add_argument("--json", action="store_true",
                    help="print the model as JSON on stdout")
    args = ap.parse_args(argv)

    if args.devices > 1:
        # Must land before the first jax.devices() call (backend init);
        # merely having imported jax is fine. If some earlier code already
        # initialized a too-small backend, _probe_mesh fails loudly.
        import re

        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)",
                      flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
        elif int(m.group(1)) < args.devices:
            # An ambient pin SMALLER than the calibration target used to
            # survive the substring check above, so the CLI promised
            # --devices N while run_probes saw the ambient count and
            # _probe_mesh failed with a mismatch naming neither side.
            # The backend is not initialized yet in this process, so the
            # flag can simply be rewritten to what the CLI was asked for.
            os.environ["XLA_FLAGS"] = flags.replace(
                m.group(0),
                f"--xla_force_host_platform_device_count={args.devices}")

    model = run_probes(devices=args.devices or None, payload=args.payload,
                       reps=args.reps, smoke=args.smoke)
    if args.out != "-":
        path = save_cost_model(model, args.out)
        print(f"cost model [{model.cache_key()}] -> {path}")
    print(model.describe())
    if args.json:
        print(json.dumps(model.to_dict(), indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    raise SystemExit(main())
