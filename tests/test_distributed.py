"""Multi-device tests. Each test runs in a subprocess with
--xla_force_host_platform_device_count so the main pytest process keeps the
single-CPU device set (dryrun.py owns the 512-device forcing).
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 480) -> str:
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


def test_runtimes_agree_on_8_devices():
    run_sub("""
        import numpy as np
        from repro.core import TaskGraph, KernelSpec, get_runtime
        for pattern in ["stencil_1d", "stencil_1d_periodic", "dom", "nearest",
                        "fft", "tree", "all_to_all", "spread",
                        "random_nearest"]:
            g = TaskGraph(steps=5, width=32, pattern=pattern, payload=8,
                          kernel=KernelSpec("compute_bound", 8), radius=2)
            ref = get_runtime("fused").execute(g)
            for name in ["bsp", "bsp_scan", "overlap"]:
                rt = get_runtime(name)
                ok, _ = rt.supports(g)
                if not ok: continue
                out = rt.execute(g)
                err = float(np.abs(out - ref).max())
                assert err < 1e-5, (pattern, name, err)
        print("ALL OK")
    """)


def test_pallas_step_multi_device_matches_fused():
    """pallas_step across real (forced-host) devices: every halo pattern,
    steps_per_launch in {1, 4, 8}, vs the fused oracle. W=16 on 4 devices
    gives B=4, so S=8 with r=1 (and any S with r=2) needs deep halos past
    the block — the multi-hop ring exchange path — and T=10 with S=4/8
    exercises the masked-tail launch. B=4 never keeps an interior, so the
    (default-on) pipeline gates itself off and launch counts stay serial."""
    run_sub("""
        import numpy as np
        from repro.core import TaskGraph, KernelSpec, get_runtime
        for pattern, radius in [("stencil_1d", 1), ("stencil_1d_periodic", 1),
                                ("dom", 1), ("nearest", 2),
                                ("random_nearest", 2), ("no_comm", 1)]:
            g = TaskGraph(steps=10, width=16, pattern=pattern, payload=8,
                          kernel=KernelSpec("compute_bound", 8),
                          radius=radius, seed=7)
            ref = get_runtime("fused").execute(g)
            for S in (1, 4, 8):
                rt = get_runtime("pallas_step", steps_per_launch=S)
                ok, why = rt.supports(g)
                assert ok, (pattern, S, why)
                out = rt.execute(g)
                err = float(np.abs(out - ref).max())
                assert err < 1e-5, (pattern, S, err)
                assert rt.dispatches_per_run(g) == 1 + -(-9 // S)
        print("ALL OK")
    """, devices=4)


def test_halo_async_exchange_parity_multi_device():
    """exchange_halos_start/join == the sync exchange_halos == a numpy
    roll oracle, for depths below a block, exactly a block, past a block
    (multi-hop), and past the whole ring (wrap), under shard_map on 4
    devices. The fused single-collective edge transport must move the
    same bits as the per-direction ppermute transport."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.core.runtimes import _halo

        D, B, Pay = 4, 6, 5
        W = D * B
        mesh = Mesh(np.array(jax.devices()), ("shard",))
        x = np.arange(W * Pay, dtype=np.float32).reshape(W, Pay)

        def run(fn):
            f = jax.jit(shard_map(fn, mesh=mesh, check_vma=False,
                                  in_specs=P("shard"),
                                  out_specs=(P("shard"), P("shard"))))
            l, r = f(jax.device_put(x, NamedSharding(mesh, P("shard"))))
            return np.asarray(l), np.asarray(r)

        for r in (2, 6, 7, 13, 29):  # r<B, r==B, multi-hop, wrap, 5x wrap
            def sync(local, r=r):
                return _halo.exchange_halos(local, r, D, "shard")

            def started(local, r=r):
                return _halo.exchange_halos_join(
                    _halo.exchange_halos_start(local, r, D, "shard"))

            sl, sr = run(sync)
            al, ar = run(started)
            assert np.array_equal(sl, al) and np.array_equal(sr, ar), r
            # oracle: rows immediately left/right of each block, mod W
            wl = np.stack([x[(np.arange(d * B - r, d * B)) % W]
                           for d in range(D)]).reshape(D * r, Pay)
            wr = np.stack([x[(np.arange((d + 1) * B, (d + 1) * B + r)) % W]
                           for d in range(D)]).reshape(D * r, Pay)
            assert np.array_equal(sl, wl) and np.array_equal(sr, wr), r

        # edge transport parity: fused all-gather vs per-direction ppermute
        for r in (1, 3, 6):
            def edges(local, r=r, impl="xla"):
                h = _halo.exchange_edges_start(
                    local[:r], local[B - r:], D, "shard", impl=impl)
                return _halo.exchange_halos_join(h)

            xl, xr = run(lambda l, r=r: edges(l, r, "xla"))
            pl_, pr = run(lambda l, r=r: edges(l, r, "ppermute"))
            assert np.array_equal(xl, pl_) and np.array_equal(xr, pr), r
        print("ALL OK")
    """, devices=4)


def test_stride_exchange_oracle_multi_device():
    """exchange_stride_start/join == the sync spelling == a numpy oracle
    (partner block of stride bs on device d = global rows of block d XOR
    bs), for single strides, the far-side stride D-1, and a multi-stride
    start served by ONE fused collective; both transports must move the
    same bits. gather_global likewise against a roll-free global oracle."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.core.runtimes import _halo

        D, B, Pay = 4, 5, 3
        W = D * B
        mesh = Mesh(np.array(jax.devices()), ("shard",))
        x = np.arange(W * Pay, dtype=np.float32).reshape(W, Pay)

        def run(fn, n_out):
            f = jax.jit(shard_map(fn, mesh=mesh, check_vma=False,
                                  in_specs=P("shard"),
                                  out_specs=(P("shard"),) * n_out))
            outs = f(jax.device_put(x, NamedSharding(mesh, P("shard"))))
            return [np.asarray(o) for o in outs]

        def oracle(bs):  # stacked partner blocks in device order
            return np.concatenate([x[(d ^ bs) * B:(d ^ bs) * B + B]
                                   for d in range(D)])

        for strides in [(1,), (2,), (3,), (1, 2, 3)]:
            def sync(local, ss=strides, impl="xla"):
                return _halo.exchange_stride(local, ss, D, "shard",
                                             impl=impl)

            def started(local, ss=strides):
                return _halo.exchange_stride_join(
                    _halo.exchange_stride_start(local, ss, D, "shard"))

            got = run(lambda l, ss=strides: sync(l, ss), len(strides))
            asy = run(lambda l, ss=strides: started(l, ss), len(strides))
            ppm = run(lambda l, ss=strides: sync(l, ss, "ppermute"),
                      len(strides))
            for j, bs in enumerate(strides):
                want = oracle(bs)
                assert np.array_equal(got[j], want), (strides, bs, "xla")
                assert np.array_equal(asy[j], want), (strides, bs, "async")
                assert np.array_equal(ppm[j], want), (strides, bs, "ppermute")

        # out-of-range strides fail loudly (0 = self, D = off the mesh)
        for bad in (0, D):
            try:
                _halo.exchange_stride_start(jnp.ones((B, Pay)), (bad,), D,
                                            "shard")
                raise AssertionError(f"stride {bad} accepted")
            except ValueError:
                pass

        # gather_global: the full global-order state on EVERY device;
        # out_specs P("shard") stacks each device's (W, Pay) result, so
        # the oracle is the global state tiled D times. Both transports
        # must match it bit-for-bit.
        for impl in ("xla", "ppermute"):
            f = jax.jit(shard_map(
                lambda l, impl=impl: (_halo.gather_global(
                    l, D, "shard", impl=impl),),
                mesh=mesh, check_vma=False, in_specs=P("shard"),
                out_specs=(P("shard"),)))
            out = np.asarray(f(jax.device_put(
                x, NamedSharding(mesh, P("shard"))))[0])
            assert np.array_equal(out, np.concatenate([x] * D)), impl
        print("ALL OK")
    """, devices=4)


def test_stride_exchange_single_device():
    """One device: every butterfly stride is in-block (no exchange), the
    primitive rejects any requested stride (there is no valid bs in
    [1, 1)), and gather_global is the identity — the degenerate cases the
    stride plan relies on."""
    run_sub("""
        import numpy as np, jax.numpy as jnp
        from repro.core.runtimes import _halo
        x = jnp.arange(12.0).reshape(6, 2)
        assert np.array_equal(np.asarray(_halo.gather_global(x, 1)), x)
        try:
            _halo.exchange_stride_start(x, (1,), 1, "shard")
            raise AssertionError("stride 1 accepted on 1 device")
        except ValueError:
            pass
        # non-power-of-two device counts are rejected loudly (d XOR bs
        # would leave the mesh; the transports would otherwise diverge)
        try:
            _halo.exchange_stride_start(x, (4,), 6, "shard")
            raise AssertionError("non-pow2 device count accepted")
        except ValueError as e:
            assert "power-of-two" in str(e)
        from repro.core import TaskGraph, KernelSpec, get_runtime
        g = TaskGraph(steps=6, width=16, payload=8, pattern="fft",
                      kernel=KernelSpec("compute_bound", 8))
        ref = get_runtime("fused").execute(g)
        out = get_runtime("pallas_step").execute(g)
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        print("ALL OK")
    """, devices=1)


def test_pallas_step_butterfly_global_multi_device():
    """Acceptance on 4 devices: fft/tree BIT-identical to fused at S in
    {1, 8} (stride plan per-step, all-gather plan blocked with per-depth
    tables); spread/all_to_all allclose at S in {1, 4}; launch accounting
    matches the executed plan; both transports bit-identical."""
    run_sub("""
        import numpy as np
        from repro.core import TaskGraph, KernelSpec, get_runtime
        for pattern in ("fft", "tree"):
            g = TaskGraph(steps=10, width=16, payload=8, pattern=pattern,
                          kernel=KernelSpec("compute_bound", 8), seed=7)
            ref = get_runtime("fused").execute(g)
            for S in (1, 8):
                rt = get_runtime("pallas_step", steps_per_launch=S)
                out = rt.execute(g)
                assert np.array_equal(out, ref), (pattern, S, "bits differ")
                want = 10 if S == 1 else 1 + -(-9 // 8)
                assert rt.dispatches_per_run(g) == want, (pattern, S)
        for pattern, kw in (("spread", dict(fanout=3)), ("all_to_all", {})):
            g = TaskGraph(steps=10, width=16, payload=8, pattern=pattern,
                          kernel=KernelSpec("compute_bound", 8), seed=7,
                          **kw)
            ref = get_runtime("fused").execute(g)
            for S in (1, 4):
                out = get_runtime("pallas_step",
                                  steps_per_launch=S).execute(g)
                err = float(np.abs(out - ref).max())
                assert err < 1e-5, (pattern, S, err)
        g = TaskGraph(steps=10, width=16, payload=8, pattern="fft",
                      kernel=KernelSpec("compute_bound", 8), seed=7)
        a = get_runtime("pallas_step").execute(g)
        b = get_runtime("pallas_step", halo_impl="ppermute").execute(g)
        assert np.array_equal(a, b)
        # mixed-plan tuple ensemble across devices
        from repro.core import GraphEnsemble
        members = [
            TaskGraph(steps=t, width=16, payload=8, pattern=p, fanout=3,
                      kernel=KernelSpec("compute_bound", 8), seed=k)
            for k, (p, t) in enumerate(
                (("stencil_1d", 6), ("fft", 4), ("spread", 10)))
        ]
        ens = GraphEnsemble(members)
        outs = get_runtime("pallas_step").execute_ensemble(ens)
        for k, (g, out) in enumerate(zip(members, outs)):
            ref = get_runtime("fused").execute(g)
            err = float(np.abs(out - ref).max())
            assert err < 1e-5, (k, err)
        print("ALL OK")
    """, devices=4)


def test_pallas_step_pipelined_multi_device():
    """The software-pipelined schedule on 4 devices: W=128 keeps a real
    interior (B=32 > 2*S*r for S=3 r=1/2 and S=8 r=1), so the pipelined
    path engages, its deep exchange rides under the interior launch, and
    every pattern — including dom's asymmetric and random_nearest's
    per-row edge masks — stays bit-identical to the pipeline=False
    ablation and allclose to fused. S=8 with r=2 (depth 16 = B/2) checks
    the structural fallback still answers correctly."""
    run_sub("""
        import numpy as np
        from repro.core import TaskGraph, KernelSpec, get_runtime
        for pattern, radius in [("stencil_1d", 1), ("stencil_1d_periodic", 1),
                                ("dom", 1), ("nearest", 2),
                                ("random_nearest", 2)]:
            g = TaskGraph(steps=10, width=128, pattern=pattern, payload=8,
                          kernel=KernelSpec("compute_bound", 8),
                          radius=radius, seed=7)
            ref = get_runtime("fused").execute(g)
            for S in (1, 3, 8):
                outs = {}
                for pipe in (True, False):
                    rt = get_runtime("pallas_step", steps_per_launch=S,
                                     pipeline=pipe)
                    out = rt.execute(g)
                    err = float(np.abs(out - ref).max())
                    assert err < 1e-5, (pattern, S, pipe, err)
                    outs[pipe] = out
                assert np.array_equal(outs[True], outs[False]), (pattern, S)
        # transport ablation stays bit-identical across devices too
        g = TaskGraph(steps=10, width=128, pattern="stencil_1d", payload=8,
                      kernel=KernelSpec("compute_bound", 8), seed=7)
        a = get_runtime("pallas_step", steps_per_launch=4).execute(g)
        b = get_runtime("pallas_step", steps_per_launch=4,
                        halo_impl="ppermute").execute(g)
        assert np.array_equal(a, b)
        print("ALL OK")
    """, devices=4)


def test_pallas_step_multi_device_blocked_ensemble():
    """Stacked hetero-steps ensemble on 4 devices with deep exchanges: one
    launch cadence, members frozen mid-launch, each matches fused. W=16
    (B=4) exercises the serial fallback, W=128 (B=32) the pipelined
    schedule — whose boundary launch batches both sides of all K members."""
    run_sub("""
        import numpy as np
        from repro.core import (GraphEnsemble, TaskGraph, KernelSpec,
                                get_runtime)
        for width in (16, 128):
            members = [TaskGraph(steps=t, width=width, payload=8,
                                 pattern="stencil_1d",
                                 kernel=KernelSpec("compute_bound", 8), seed=k)
                       for k, t in enumerate((3, 10, 6))]
            ens = GraphEnsemble(members)
            for S in (1, 4):
                for pipe in (True, False):
                    rt = get_runtime("pallas_step", steps_per_launch=S,
                                     pipeline=pipe)
                    outs = rt.execute_ensemble(ens)
                    for k, (g, out) in enumerate(zip(members, outs)):
                        ref = get_runtime("fused").execute(g)
                        err = float(np.abs(out - ref).max())
                        assert err < 1e-5, (width, S, pipe, k, err)
        print("ALL OK")
    """, devices=4)


def test_overlap_schedule_has_collective_compute_overlap():
    """The lowered HLO of the overlap runtime must not serialize the halo
    exchange after all compute: interior FMA work is independent of the
    ppermute (checked structurally: both appear in the scan body)."""
    run_sub("""
        from repro.core import TaskGraph, KernelSpec, get_runtime
        import jax
        g = TaskGraph(steps=4, width=64, pattern="stencil_1d", payload=8,
                      kernel=KernelSpec("compute_bound", 16))
        rt = get_runtime("overlap")
        fn = rt.build(g)
        import jax.numpy as jnp
        from repro.core.task_kernels import initial_state
        x = initial_state(g.width, g.payload)
        txt = jax.jit(lambda v: fn(v)).lower(x).as_text()
        assert ("collective_permute" in txt) or ("collective-permute" in txt)
        print("OK")
    """)


def test_train_step_on_2x2_mesh_runs_and_matches_single():
    """Loss on a (data=2, model=2) mesh == single-device loss (SPMD is
    semantics-preserving)."""
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs.registry import get_config, get_shape
        from repro.distributed.api import sharding_context
        from repro.distributed.sharding import ShardingPolicy
        from repro.launch import steps as S
        from repro.launch.mesh import make_host_mesh
        from repro.models.model import Model
        from repro.optim.optimizer import AdamW
        from repro.data.pipeline import SyntheticTokenPipeline

        cfg = get_config("internlm2-1.8b").reduced()
        shape = get_shape("train_4k")
        model, opt = Model(cfg), AdamW()
        params = model.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        pipe = SyntheticTokenPipeline(cfg, shape, batch_override=4,
                                      seq_override=32)
        batch = pipe.batch_at(0)
        step = S.make_train_step(model, opt)

        # single device
        p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

        # 2x2 mesh
        mesh = make_host_mesh((2, 2), ("data", "model"))
        policy = ShardingPolicy.for_step(cfg, shape, mesh)
        def wrapped(p, o, b):
            with sharding_context(mesh, policy.rules):
                return step(p, o, b)
        pm = jax.device_put(params, policy.param_shardings(params))
        om = jax.device_put(opt_state, opt.state_shardings(policy, params))
        bm = {k: jax.device_put(v, policy.batch_shardings(batch)[k])
              for k, v in batch.items()}
        p2, o2, m2 = jax.jit(wrapped)(pm, om, bm)

        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) / max(abs(l1), 1e-9) < 1e-4, (l1, l2)
        # params after one step match too
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-3)
        print("OK", l1, l2)
    """, devices=4)


def test_sequence_parallel_decode_matches_local():
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.distributed.collectives import (
            sequence_parallel_decode_attention)
        from repro.kernels import ops
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh((4,), ("model",))
        B, Hq, Hkv, S, D = 2, 8, 2, 64, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, Hq, D))
        kc = jax.random.normal(ks[1], (B, Hkv, S, D))
        vc = jax.random.normal(ks[2], (B, Hkv, S, D))
        lengths = jnp.array([50, 64], jnp.int32)
        # GQA flash-decode expects q grouped under kv heads; replicate layout
        qk = q.reshape(B, Hkv, Hq // Hkv, D).reshape(B, Hq, D)
        want = ops.decode_attention(qk, kc, vc, lengths, use_kernel=False)
        got = sequence_parallel_decode_attention(
            qk, kc, vc, lengths, mesh=mesh, seq_axes="model",
            use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        # windowed too
        want_w = ops.decode_attention(qk, kc, vc, lengths, window=16,
                                      use_kernel=False)
        got_w = sequence_parallel_decode_attention(
            qk, kc, vc, lengths, mesh=mesh, seq_axes="model", window=16,
            use_kernel=False)
        np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """, devices=4)


def test_pipeline_parallel_equals_sequential():
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline_forward
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh((4,), ("stage",))
        S, M, mb, d = 4, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        w = jax.random.normal(ks[0], (S, d, d)) * (1.0 / np.sqrt(d))
        x = jax.random.normal(ks[1], (M, mb, d))

        def stage_fn(wi, h):
            return jnp.tanh(h @ wi)

        got = pipeline_forward(stage_fn, w, x, mesh=mesh, axis="stage")
        # sequential reference
        h = x
        for s in range(S):
            h = jnp.tanh(h @ w[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """, devices=4)


def test_grad_compression_int8_cross_pod():
    run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.grad_compression import cross_pod_mean_int8
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh((2, 2), ("pod", "data"))
        g = jax.random.normal(jax.random.PRNGKey(0), (2, 64))  # per-pod grads
        ef = jnp.zeros((2, 64))
        key = jax.random.PRNGKey(1)

        def local(gs, efs, k):
            out, new_ef = cross_pod_mean_int8(gs[0], efs[0], k, axis="pod")
            return out[None], new_ef[None]

        fn = jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=(P("pod"), P("pod"), P()), out_specs=(P("pod"), P("pod")),
        ))
        out, new_ef = fn(g, ef, key)
        want = jnp.mean(g, axis=0)
        got0 = np.asarray(out[0])
        # int8 quantization error bounded by scale
        scale = float(jnp.max(jnp.abs(g)) / 127.0)
        assert np.abs(got0 - np.asarray(want)).max() < 2 * scale
        # error feedback: ef' carries the residual => repeated rounds unbiased
        accum = np.zeros(64); ef_now = ef
        for i in range(64):
            out, ef_now = fn(g, ef_now, jax.random.fold_in(key, i))
            accum += np.asarray(out[0])
        accum /= 64
        assert np.abs(accum - np.asarray(want)).max() < 0.5 * scale
        print("OK")
    """, devices=4)


def test_spec_resolution_divisibility_guard():
    run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.distributed.api import ShardingRules, sharding_context, \
            spec_for
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh((4,), ("model",))
        rules = ShardingRules({"heads": "model", "ff": "model"})
        with sharding_context(mesh, rules):
            # 25 heads don't divide 4 -> replicated; 32 does -> sharded
            assert spec_for((25, 8), ("heads", None)) == P()
            assert spec_for((32, 8), ("heads", None)) == P("model")
        print("OK")
    """, devices=4)


def test_hierarchical_multipod_train_reduced():
    """Reduced multi-pod mesh (2,2,2): train step runs; grads flow over pod
    axis; loss finite."""
    run_sub("""
        import jax, numpy as np
        from repro.configs.registry import get_config, get_shape
        from repro.launch.train import train
        from repro.launch.mesh import make_host_mesh

        cfg = get_config("internlm2-1.8b").reduced()
        shape = get_shape("train_4k")
        mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
        res = train(cfg, shape, steps=3, batch=8, seq=16, mesh=mesh,
                    verbose=False, profile=False)
        assert res.steps_run == 3
        assert np.isfinite(res.final_loss)
        print("OK", res.final_loss)
    """, devices=8)


def test_resilient_ensemble_recovery_on_4_devices():
    """The PR-8 acceptance criterion at real (forced-host) device count:
    every fault class injected into the resilient executor on a 4-device
    mesh recovers bit-identically — transport/launch/straggler against the
    clean run, member death against the truncated-steps oracle."""
    run_sub("""
        import dataclasses, numpy as np
        from repro.core import GraphEnsemble, KernelSpec, TaskGraph, \\
            get_runtime
        from repro.resilience import (FaultPlan, FaultSpec, run_resilient)

        def mk(steps, seed):
            return TaskGraph(steps=steps, width=16, pattern="stencil_1d",
                             payload=16, radius=1, seed=seed,
                             kernel=KernelSpec("compute_bound", 4))

        ens = GraphEnsemble((mk(13, 0), mk(9, 1)))
        rt = get_runtime("pallas_step", steps_per_launch=4)
        clean = [np.asarray(o) for o in rt.execute_ensemble(ens)]
        for spec in [FaultSpec("transport", 1, times=2),
                     FaultSpec("launch", 1, mode="raise"),
                     FaultSpec("launch", 2, mode="poison"),
                     FaultSpec("straggler", 1, delay_s=0.001)]:
            res = run_resilient(rt, ens, plan=FaultPlan((spec,)))
            for got, ref in zip(res.outputs, clean):
                assert np.array_equal(got, ref), spec
        res = run_resilient(
            rt, ens, plan=FaultPlan((FaultSpec("member", 1, member=1),)))
        frozen = res.evicted[1]
        oracle = rt.execute_ensemble(GraphEnsemble(
            (mk(13, 0), dataclasses.replace(mk(9, 1), steps=frozen))))
        for got, ref in zip(res.outputs, oracle):
            assert np.array_equal(got, np.asarray(ref))
        print("OK frozen@", frozen)
    """, devices=4)


def test_gather_transports_match_monolithic_oracle_16_devices():
    """PR 9: the chunked hierarchical gather at D=16 (chunk group 4, a
    real two-stage split) is bit-identical to the monolithic all-gather
    AND to the numpy global-order oracle — gathers move exact row copies,
    so any reordering in the segment/stride stages would show as an exact
    mismatch here, not a tolerance failure."""
    run_sub("""
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core.runtimes import _halo

        D = 16
        mesh = Mesh(np.array(jax.devices()[:D]), ("shard",))
        W, payload = 64, 3
        x = jnp.arange(W * payload, dtype=jnp.float32).reshape(W, payload)
        oracle = np.asarray(x)
        assert _halo.gather_chunk_group(D) == 4
        outs = {}
        for impl in ("xla", "ppermute", "chunked"):
            fn = jax.jit(shard_map(
                lambda l, impl=impl: _halo.gather_global(
                    l, D, "shard", impl=impl),
                mesh=mesh, in_specs=P("shard"), out_specs=P(None),
                check_vma=False))
            out = np.asarray(fn(x))
            assert out.shape == oracle.shape, impl
            assert (out == oracle).all(), impl
            outs[impl] = out
        assert (outs["chunked"] == outs["xla"]).all()
        print("OK")
    """, devices=16)


def test_pallas_step_deep_halo_multihop_8_devices():
    """PR 9: W=32 on 8 devices gives B=4, so S=8 with r=1 (and S=4 with
    r=2) needs halo depth past a whole neighbor block — the multi-hop
    ring path — at a device count where a hop crosses real (forced-host)
    device boundaries twice."""
    run_sub("""
        import numpy as np
        import jax
        from repro.core import TaskGraph, KernelSpec, get_runtime

        devs = jax.devices()[:8]
        for pattern, radius, S in [("stencil_1d", 1, 8), ("nearest", 2, 4)]:
            g = TaskGraph(steps=16, width=32, payload=8, pattern=pattern,
                          radius=radius,
                          kernel=KernelSpec("compute_bound", 4))
            ref = get_runtime("fused").execute(g)
            rt = get_runtime("pallas_step", devices=devs,
                             steps_per_launch=S)
            ok, why = rt.supports(g)
            assert ok, why
            out = rt.execute(g)
            np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                       err_msg=(pattern, S))
        print("OK")
    """, devices=8)


@pytest.mark.parametrize("devices,dk", [(8, 2), (16, 4)])
def test_pallas_step_member_sharded_bit_identical(devices, dk):
    """PR 9 tentpole: the K-sharded stacked ensemble on the 2D (row,
    member) mesh — D devices as (Dr, Dk) — is bit-identical to the
    replicated baseline on Dr devices (same per-device block width, so
    identical arithmetic), through both the clean run AND a resilient run
    with one member evicted mid-flight (the PR 8 act-mask semantics must
    survive the member shard)."""
    run_sub(f"""
        import numpy as np
        import jax
        from repro.core import (TaskGraph, KernelSpec, GraphEnsemble,
                                get_runtime)
        from repro.resilience.engine import run_resilient
        from repro.resilience.faults import (FaultPlan, FaultSpec,
                                             FAULT_MEMBER)

        D, dk = {devices}, {dk}
        Dr = D // dk
        devs = jax.devices()
        members = [TaskGraph(steps=8, width=4 * Dr, payload=8,
                             pattern="stencil_1d", radius=1, seed=k,
                             kernel=KernelSpec("compute_bound", 2))
                   for k in range(2 * dk)]
        ens = GraphEnsemble(members)
        rep = get_runtime("pallas_step", devices=devs[:Dr],
                          steps_per_launch=2)
        ksh = get_runtime("pallas_step", devices=devs[:D],
                          steps_per_launch=2, member_shards=dk)
        ok, why = ksh.supports_ensemble(ens)
        assert ok, why
        for u, v in zip(rep.execute_ensemble(ens),
                        ksh.execute_ensemble(ens)):
            u, v = np.asarray(u), np.asarray(v)
            assert u.shape == v.shape and (u == v).all()
        plan = FaultPlan((FaultSpec(FAULT_MEMBER, 2, member=1),))
        f_rep = run_resilient(rep, ens, plan=plan)
        f_ksh = run_resilient(ksh, ens, plan=plan)
        assert f_rep.evicted == f_ksh.evicted
        for u, v in zip(f_rep.outputs, f_ksh.outputs):
            u, v = np.asarray(u), np.asarray(v)
            assert u.shape == v.shape and (u == v).all()
        print("OK")
    """, devices=devices)


def test_member_shards_guard_names_fallback():
    """The 2D mesh builder and the runtime's member_shards resolution
    reject a non-dividing Dk LOUDLY, naming member_shards=1 as the
    fallback (mirroring exchange_stride_start's non-pow2 rejection) —
    never an opaque reshape error from inside shard_map."""
    run_sub("""
        import jax
        from repro.core import TaskGraph, KernelSpec, GraphEnsemble, get_runtime
        from repro.launch.mesh import make_row_member_mesh

        devs = jax.devices()[:8]
        try:
            make_row_member_mesh(devs, 3)
            raise SystemExit("expected ValueError for Dk=3 over 8 devices")
        except ValueError as e:
            assert "member_shards=1" in str(e), e
        members = [TaskGraph(steps=4, width=32, payload=8,
                             pattern="stencil_1d", radius=1, seed=k,
                             kernel=KernelSpec("compute_bound", 1))
                   for k in range(4)]
        try:
            get_runtime("pallas_step", devices=devs,
                        member_shards=3).execute_ensemble(
                            GraphEnsemble(members))
            raise SystemExit("expected ValueError for member_shards=3, K=4")
        except ValueError as e:
            assert "member_shards=1" in str(e), e
        print("OK")
    """, devices=8)
