"""Property-based serving suite: ANY packer schedule is bit-exact.

The serving extension of test_chaos_property.py's eviction oracle: for
any drawn (pattern x slots x steps_per_launch x request schedule) —
staggered arrivals, priorities, explicit deadlines that may or may not
expire mid-cohort — the fabric's continuous-batching run (retirements
freeing act-mask slots, queued requests re-admitted mid-run via
``admit_fn``) must reproduce each request's SERIAL execution bit for bit.
The oracle is the same-K uniform ensemble truncated to the request's
effective horizon — exactly the convention the chaos suite's member
eviction check established — and the fabric's ``verify=True`` path
asserts it per request; the property test asserts the aggregate never
degrades to "close enough" float noise for any schedule.

Runs on the virtual LaunchClock (time = launch count) so schedules are
deterministic and hypothesis shrinking is meaningful. Shapes stay small:
every drawn case compiles its cohort launch plans plus oracle ensembles.

The multi-device leg runs the fabric on 4 forced-host devices in a
subprocess (test_distributed.py's pattern) and also pins the chunked
gather's forced-grouping bit-identity, since serving rows ride the same
gather transports.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import get_runtime
from repro.serving import LaunchClock, ServingFabric, make_request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WIDTH = 8
PATTERNS = ("stencil_1d", "nearest")

#: (steps, arrival in launch units, priority, deadline offset or None)
REQ = st.tuples(st.integers(3, 11), st.integers(0, 6), st.integers(0, 2),
                st.sampled_from((None, 3.0, 9.0)))


@given(pattern=st.sampled_from(PATTERNS),
       slots=st.integers(2, 3),
       spl=st.sampled_from((1, 4)),
       drawn=st.lists(REQ, min_size=3, max_size=6))
@settings(max_examples=10, deadline=None, derandomize=True)
def test_any_packer_schedule_is_bit_identical(pattern, slots, spl, drawn):
    radius = 2 if pattern == "nearest" else 1
    reqs = [make_request(
        rid, steps=steps, width=WIDTH, pattern=pattern, radius=radius,
        seed=17 * rid + 1, arrival_s=float(arrival),
        deadline_s=float(arrival) + dl if dl is not None else None,
        priority=priority)
        for rid, (steps, arrival, priority, dl) in enumerate(drawn)]
    rt = get_runtime("pallas_step", steps_per_launch=spl)
    fabric = ServingFabric(rt, max_slots=slots, verify=True,
                           clock=LaunchClock())
    rep = fabric.serve(reqs)
    assert len(rep.outcomes) == len(reqs)
    # EVERY outcome — completed or deadline-evicted at its frozen
    # horizon — matches its serial same-K oracle exactly
    for o in rep.outcomes:
        assert o.bit_identical is True, (o.rid, o.status, o.effective_steps)
    assert all((c.recompiles or 0) == 0 for c in rep.cohorts)
    for o in rep.outcomes:
        if o.status == "completed":
            assert o.effective_steps == reqs[o.rid].graph.steps
        else:
            assert o.status == "deadline_evicted"
            assert o.effective_steps <= reqs[o.rid].graph.steps


def run_sub(code: str, devices: int = 4, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_fabric_on_four_devices():
    """The full serving loop — packing, mid-run re-admission, deadline
    pricing — on a real 4-device mesh, bit-identity asserted in-process
    by verify=True; plus forced chunk groupings of the hierarchical
    gather staying exact (every G | D is the same rows, only the
    rendezvous anatomy differs)."""
    run_sub("""
        import numpy as np
        from repro.core import get_runtime
        from repro.core.runtimes import _halo
        from repro.serving import LaunchClock, ServingFabric, make_request
        import jax, jax.numpy as jnp

        devs = jax.devices()[:4]
        rt = get_runtime("pallas_step", devices=devs, steps_per_launch=2)
        reqs = [make_request(0, steps=9, width=16, seed=1),
                make_request(1, steps=5, width=16, seed=2),
                make_request(2, steps=7, width=16, seed=3, arrival_s=1.0),
                make_request(3, steps=5, width=16, pattern="nearest",
                             radius=2, seed=4, arrival_s=1.0)]
        rep = ServingFabric(rt, max_slots=2, verify=True,
                            clock=LaunchClock()).serve(reqs)
        assert rep.bit_identical is True, [
            (o.rid, o.bit_identical) for o in rep.outcomes]
        stacked = [c for c in rep.cohorts if c.kind == "stacked"]
        assert len(stacked) == 2, [c.kind for c in rep.cohorts]
        assert sum(c.admitted_mid_run for c in stacked) >= 1
        assert all((c.recompiles or 0) == 0 for c in rep.cohorts)

        # forced chunk groupings are bit-identical to the monolithic path
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(devs), ("shard",))
        x = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
        ref = np.asarray(x)
        for g in (1, 2, 4):  # 1 and 4 degrade to the monolithic path
            fn = jax.jit(shard_map(
                lambda l, g=g: _halo.gather_global(
                    l, 4, "shard", impl="chunked", chunk_group=g),
                mesh=mesh, in_specs=P("shard"), out_specs=P(None),
                check_vma=False))
            assert np.array_equal(np.asarray(fn(x)), ref), g
        print("SERVE-4D OK")
    """)
