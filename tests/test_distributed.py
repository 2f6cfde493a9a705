"""Multi-device behaviour (subprocess with 8 forced host devices).

The main pytest process keeps the single real CPU device (see
conftest.py); everything here runs in fresh subprocesses with
``--xla_force_host_platform_device_count=8``.
"""

import textwrap

import pytest


def _check(subproc, code, devices=8):
    r = subproc(textwrap.dedent(code), devices=devices)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_faces_engines_match_oracle(subproc):
    _check(subproc, """
        import numpy as np, jax
        from repro.core import (FacesConfig, FusedEngine, HostEngine,
                                build_faces_program, faces_oracle)
        from repro.parallel import make_mesh
        mesh = make_mesh((2, 2, 2), ("gx", "gy", "gz"))
        cfg = FacesConfig(grid=(2, 2, 2), points=(5, 4, 3))
        prog = build_faces_program(cfg, mesh)
        u0 = np.random.RandomState(0).randn(2, 2, 2, 5, 4, 3).astype(np.float32)
        ref = faces_oracle(u0, cfg)
        for mode in ("stream", "dataflow"):
            eng = FusedEngine(prog, mode=mode)
            out = eng(eng.init_buffers({"u": u0}))
            np.testing.assert_allclose(np.asarray(out["u"]), ref, rtol=1e-5, atol=1e-5)
        host = HostEngine(prog)
        out = host(host.init_buffers({"u": u0}))
        np.testing.assert_allclose(np.asarray(out["u"]), ref, rtol=1e-5, atol=1e-5)
        assert host.stats.dispatches == prog.dispatch_count_host()
        assert host.stats.sync_points >= host.stats.dispatches
    """)


@pytest.mark.slow
def test_faces_fused_equals_host_bitwise_pathwise(subproc):
    """The two engines are the paper's A/B: results must agree exactly
    (same math, different control path)."""
    _check(subproc, """
        import numpy as np, jax
        from repro.core import FacesConfig, FusedEngine, HostEngine, build_faces_program
        from repro.parallel import make_mesh
        mesh = make_mesh((4, 1, 2), ("gx", "gy", "gz"))
        cfg = FacesConfig(grid=(4, 1, 2), points=(4, 3, 5), periodic=True)
        prog = build_faces_program(cfg, mesh)
        u0 = np.random.RandomState(1).randn(4, 1, 2, 4, 3, 5).astype(np.float32)
        f = FusedEngine(prog, mode="dataflow"); h = HostEngine(prog)
        a = f(f.init_buffers({"u": u0}))["u"]
        b = h(h.init_buffers({"u": u0}))["u"]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
    """)


@pytest.mark.slow
def test_staged3_matches_its_oracle(subproc):
    """Staged (3-sweep) halo: each sweep's sum equals a numpy emulation."""
    _check(subproc, """
        import numpy as np, jax
        from repro.core import FacesConfig, FusedEngine, build_faces_program
        from repro.core.halo import FACES, _region_for
        from repro.parallel import make_mesh
        mesh = make_mesh((2, 2, 2), ("gx", "gy", "gz"))
        cfg = FacesConfig(grid=(2, 2, 2), points=(4, 4, 4), granularity="staged3",
                          interior_compute=False)
        prog = build_faces_program(cfg, mesh)
        u0 = np.random.RandomState(2).randn(2, 2, 2, 4, 4, 4).astype(np.float32)
        eng = FusedEngine(prog, mode="stream")
        out = np.asarray(eng(eng.init_buffers({"u": u0}))["u"])

        # numpy emulation of the same staged schedule
        ref = u0.copy()
        for axis in (0, 1, 2):
            dirs = [d for d in FACES if d[axis] != 0]
            packed = {d: ref[(slice(None),)*3 + _region_for(d, cfg.points)].copy()
                      for d in dirs}
            for d in dirs:
                msg = packed[d]
                shifted = np.zeros_like(msg)
                src = [slice(None)]*6; dst = [slice(None)]*6
                n = (2, 2, 2)[axis]; delta = d[axis]
                if delta > 0:
                    src[axis] = slice(0, n - delta); dst[axis] = slice(delta, n)
                else:
                    src[axis] = slice(-delta, n); dst[axis] = slice(0, n + delta)
                shifted[tuple(dst)] = msg[tuple(src)]
                region = _region_for(tuple(-x for x in d), cfg.points)
                ref[(slice(None),)*3 + region] += shifted
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    """)


@pytest.mark.slow
def test_overlap_collectives_match_lax(subproc):
    _check(subproc, """
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from repro.core import overlap
        from repro.parallel import make_mesh
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh((8,), ("x",))
        x = np.random.RandomState(0).randn(32, 16).astype(np.float32)

        def smap(f, in_spec, out_spec):
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_spec,
                                         out_specs=out_spec, check_vma=False))

        # all_gather_ring (both directions) == lax.all_gather
        for bidi in (False, True):
            got = smap(partial(overlap.all_gather_ring, axis="x", bidirectional=bidi),
                       (P("x"),), P())(x)
            np.testing.assert_allclose(np.asarray(got), x, rtol=1e-6)

        # reduce_scatter_ring == psum_scatter
        got = smap(partial(overlap.reduce_scatter_ring, axis="x"),
                   (P(None, None),), P("x"))(x)
        want = smap(lambda v: jax.lax.psum_scatter(v, "x", scatter_dimension=0,
                                                   tiled=True),
                    (P(None, None),), P("x"))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)

        # all_gather_matmul == (all_gather @ w)
        w = np.random.RandomState(1).randn(16, 8).astype(np.float32)
        got = smap(partial(overlap.all_gather_matmul, axis="x"),
                   (P("x"), P()), P())(x, w)
        np.testing.assert_allclose(np.asarray(got), x @ w, rtol=1e-4, atol=1e-5)

        # matmul_reduce_scatter == reduce_scatter(x_part @ w_part)
        xk = np.random.RandomState(2).randn(32, 64).astype(np.float32)
        wk = np.random.RandomState(3).randn(64, 8).astype(np.float32)
        got = smap(partial(overlap.matmul_reduce_scatter, axis="x"),
                   (P(None, "x"), P("x")), P("x"))(xk, wk)
        full = xk @ wk
        np.testing.assert_allclose(np.asarray(got), full, rtol=1e-4, atol=1e-4)

        # all_to_all_ppermute == lax.all_to_all
        xa = np.random.RandomState(4).randn(64, 4).astype(np.float32)
        got = smap(partial(overlap.all_to_all_ppermute, axis="x"),
                   (P("x"),), P("x"))(xa)
        want = smap(lambda v: jax.lax.all_to_all(v, "x", split_axis=0,
                                                 concat_axis=0, tiled=True),
                    (P("x"),), P("x"))(xa)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
        print("overlap OK")
    """)


@pytest.mark.slow
def test_sharded_train_step_runs(subproc):
    """2x2 (data x model) sharded train step on the smallest arch."""
    _check(subproc, """
        import numpy as np, jax
        from repro.configs.base import ShapeConfig, get_config
        from repro.launch.train import train
        from repro.optim import AdamWConfig
        from repro.parallel import make_mesh
        cfg = get_config("qwen1.5-0.5b").smoke()
        mesh = make_mesh((2, 2), ("data", "model"))
        shape = ShapeConfig("t", 32, 4, "train")
        params, opt_state, hist = train(cfg, shape, mesh, steps=6,
                                        opt=AdamWConfig(lr=1e-3), log_every=5)
        losses = [h["loss"] for h in hist]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0] + 0.5  # not diverging
    """, devices=4)


@pytest.mark.slow
def test_fused_engine_lowers_single_program(subproc):
    """The ST engine's whole program is ONE executable; the host engine
    dispatches per descriptor (the paper's control-path contrast)."""
    _check(subproc, """
        from repro.core import FacesConfig, FusedEngine, HostEngine, build_faces_program
        from repro.parallel import make_mesh
        import numpy as np
        mesh = make_mesh((2, 2, 2), ("gx", "gy", "gz"))
        cfg = FacesConfig(grid=(2, 2, 2), points=(4, 4, 4))
        prog = build_faces_program(cfg, mesh)
        eng = FusedEngine(prog, mode="dataflow")
        lowered = eng.lower()
        text = lowered.as_text()
        assert "collective" in text or "ppermute" in text  # comm present
        host = HostEngine(prog)
        out = host(host.init_buffers({"u": np.ones((2,2,2,4,4,4), np.float32)}))
        assert host.stats.dispatches == prog.dispatch_count_host() > 1
    """)


@pytest.mark.slow
def test_expert_parallel_moe_matches_gather(subproc):
    """shard_map EP dispatch == auto-partitioned gather dispatch (ample
    capacity ⇒ no drops ⇒ identical math) on a data×model mesh."""
    _check(subproc, """
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import get_config
        from repro.models import Model, moe as moe_lib
        from repro.parallel import RULES_TRAIN, make_mesh, sharding_ctx
        cfg = dataclasses.replace(get_config("deepseek-v3-671b").smoke(),
                                  capacity_factor=8.0)
        m = Model(cfg)
        params, _ = m.init(jax.random.PRNGKey(0))
        p = params["decoder"]["segments"][1][0]["moe"]
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 8, cfg.d_model), jnp.float32)
        mesh = make_mesh((2, 2), ("data", "model"))
        y_gather, _ = moe_lib.apply_moe(
            dataclasses.replace(cfg, moe_impl="gather") and p, x,
            dataclasses.replace(cfg, moe_impl="gather"))
        with mesh, sharding_ctx(RULES_TRAIN, mesh):
            out = moe_lib.apply_moe_ep(p, x, cfg)
            assert out is not None, "EP path did not engage"
            y_ep, aux = out
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_gather),
                                   rtol=2e-4, atol=2e-4)
        assert float(aux["dropped_frac"]) == 0.0
        print("EP == gather OK")
    """, devices=4)


@pytest.mark.slow
def test_expert_parallel_moe_virtual_experts(subproc):
    """E < model-axis (grok case): F-split virtual experts == gather."""
    _check(subproc, """
        import dataclasses, numpy as np, jax, jax.numpy as jnp
        from repro.configs.base import get_config
        from repro.models import Model, moe as moe_lib
        from repro.parallel import RULES_TRAIN, make_mesh, sharding_ctx
        cfg = dataclasses.replace(get_config("grok-1-314b").smoke(),
                                  n_experts=2, top_k=1, capacity_factor=8.0,
                                  d_ff_expert=64)
        m = Model(cfg)
        params, _ = m.init(jax.random.PRNGKey(0))
        p = params["decoder"]["segments"][0][0]["moe"]
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 8, cfg.d_model), jnp.float32)
        mesh = make_mesh((1, 4), ("data", "model"))  # E=2 < model=4 → r=2
        y_gather, _ = moe_lib.apply_moe(
            p, x, dataclasses.replace(cfg, moe_impl="gather"))
        with mesh, sharding_ctx(RULES_TRAIN, mesh):
            out = moe_lib.apply_moe_ep(p, x, cfg)
            assert out is not None, "virtual-expert EP did not engage"
            y_ep, aux = out
        np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_gather),
                                   rtol=2e-4, atol=2e-4)
        print("virtual-expert EP OK")
    """, devices=4)
