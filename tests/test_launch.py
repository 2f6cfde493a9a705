"""Launcher-layer tests: HLO analysis, step builders, mesh, counting."""

import numpy as np
import pytest

from repro.launch.hlo_analysis import (
    analyze_collectives,
    analyze_dots,
    _tensor_bytes,
)


class TestTensorBytes:
    def test_simple(self):
        assert _tensor_bytes("bf16[2,3]") == 12
        assert _tensor_bytes("f32[128]") == 512
        assert _tensor_bytes("f32[]") == 4

    def test_tuple(self):
        assert _tensor_bytes("(bf16[2,2], f32[4])") == 8 + 16

    def test_unknown_dtype_ignored(self):
        assert _tensor_bytes("token[]") == 0


HLO_SAMPLE = """
ENTRY %main (p0: f32[64,32]) -> f32[64,32] {
  %p0 = f32[64,32] parameter(0)
  %ag = f32[64,32] all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[64,32] all-reduce(%ag), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[64,32] collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
  ROOT %rs = f32[64,32] reduce-scatter(%cp), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""


class TestCollectives:
    def test_kinds_and_counts(self):
        stats = analyze_collectives(HLO_SAMPLE, 4)
        assert stats.count_by_kind == {
            "all-gather": 1, "all-reduce": 1, "collective-permute": 1,
            "reduce-scatter": 1}

    def test_wire_byte_conventions(self):
        stats = analyze_collectives(HLO_SAMPLE, 4)
        nbytes = 64 * 32 * 4
        frac = 3 / 4
        assert np.isclose(stats.bytes_by_kind["all-gather"], nbytes * frac)
        assert np.isclose(stats.bytes_by_kind["all-reduce"], 2 * nbytes * frac)
        assert np.isclose(stats.bytes_by_kind["reduce-scatter"], nbytes * frac)
        assert np.isclose(stats.bytes_by_kind["collective-permute"], nbytes)


DOT_SAMPLE = """
ENTRY %main (p0: f32[8,16], p1: f32[16,4]) -> f32[8,4] {
  %p0 = f32[8,16] parameter(0)
  %p1 = f32[16,4] parameter(1)
  ROOT %dot.1 = f32[8,4] dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
%other (a: f32[2,3]) -> f32[2,2] {
  %a = f32[2,3] parameter(0)
  ROOT %dot.2 = f32[2,2] dot(%a, %a), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
"""


class TestDots:
    def test_flops_and_scoping(self):
        stats = analyze_dots(DOT_SAMPLE)
        assert stats.n_dots == 2
        # 2*8*4*16 + 2*2*2*3
        assert stats.total_flops == 2 * 8 * 4 * 16 + 2 * 2 * 2 * 3


class TestCounting:
    def test_param_counts_match_published(self):
        from repro.configs import get_config
        from repro.models.counting import count_params
        expect = {
            "qwen1.5-0.5b": (0.46e9, 0.47e9),
            "deepseek-v3-671b": (6.6e11, 6.8e11),
            "grok-1-314b": (3.0e11, 3.3e11),
            "glm4-9b": (9.0e9, 9.6e9),
            "mamba2-2.7b": (2.6e9, 2.8e9),
        }
        for arch, (lo, hi) in expect.items():
            n = count_params(get_config(arch))
            assert lo <= n <= hi, (arch, n)

    def test_active_less_than_total_for_moe(self):
        from repro.configs import get_config
        from repro.models.counting import count_params
        for arch in ("deepseek-v3-671b", "grok-1-314b"):
            cfg = get_config(arch)
            assert count_params(cfg, True) < 0.5 * count_params(cfg)

    def test_model_flops_monotone_in_shape(self):
        from repro.configs import get_config
        from repro.configs.base import SHAPES
        from repro.models.counting import model_flops
        cfg = get_config("glm4-9b")
        train = model_flops(cfg, SHAPES["train_4k"])["model_flops"]
        prefill = model_flops(cfg, SHAPES["prefill_32k"])["model_flops"]
        decode = model_flops(cfg, SHAPES["decode_32k"])["model_flops"]
        assert train > prefill > decode > 0


@pytest.mark.slow
def test_step_bundle_lowers_on_small_mesh(subproc):
    """build_bundle lowers train/prefill/serve for a smoke config on a
    4-device data×model mesh (mini version of the 512-chip dry-run)."""
    r = subproc("""
import dataclasses, jax
from repro.configs.base import ShapeConfig, get_config
from repro.launch.steps import build_bundle
from repro.parallel import make_mesh
cfg = dataclasses.replace(get_config("qwen1.5-0.5b").smoke(), vocab=512)
mesh = make_mesh((2, 2), ("data", "model"))
for shape in (ShapeConfig("t", 32, 4, "train"),
              ShapeConfig("p", 32, 4, "prefill"),
              ShapeConfig("d", 64, 4, "decode")):
    bundle = build_bundle(cfg, shape, mesh)
    compiled = bundle.lower().compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax wraps the dict in a list
        cost = cost[0]
    assert cost["flops"] > 0
    print(shape.kind, "ok")
""", devices=4)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("ok") == 3


def test_production_mesh_requires_512_devices():
    """make_production_mesh fails cleanly without forced device count
    (this test runs with the single real device)."""
    from repro.launch.mesh import make_production_mesh
    with pytest.raises(ValueError):
        make_production_mesh()


@pytest.mark.slow
def test_persistent_step_bundle_matches_sequential(subproc):
    """persistent_steps folds N train steps into one dispatch whose
    result matches N sequential jitted steps (same batch regime)."""
    r = subproc("""
import numpy as np, jax
from repro.configs.base import ShapeConfig, get_config
from repro.data.synthetic import SyntheticTokens
from repro.launch import build_persistent_train_step, build_train_step
from repro.optim import AdamWConfig, adamw_init
from repro.parallel import make_mesh

cfg = get_config("qwen1.5-0.5b").smoke()
mesh = make_mesh((1,), ("data",))
shape = ShapeConfig("t", 8, 4, "train")
b1 = build_train_step(cfg, shape, mesh)
bN = build_persistent_train_step(cfg, shape, mesh, n_iters=3)
params, _ = b1.model.init(jax.random.PRNGKey(0))
opt_state = adamw_init(params, AdamWConfig())
batch = {k: jax.numpy.asarray(v)
         for k, v in SyntheticTokens(cfg, shape).batch(0).items()}
with mesh:
    p, o = params, opt_state
    j1 = jax.jit(b1.step_fn)
    losses = []
    for _ in range(3):
        p, o, met = j1(p, o, batch)
        losses.append(float(met["loss"]))
    pN, oN, metN = jax.jit(bN.step_fn)(params, opt_state, batch)
assert int(metN["steps_done"]) == 3
# stacked metrics carry: the whole per-step loss trace comes back
np.testing.assert_allclose(np.asarray(metN["loss"], np.float64), losses,
                           rtol=1e-4)
for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(pN)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=2e-3, atol=2e-3)
print("persistent bundle ok")
""", devices=1)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "persistent bundle ok" in r.stdout


def _toy_bundle():
    import jax.numpy as jnp

    from repro.launch.steps import StepBundle

    def toy_step(params, opt_state, batch):
        new_p = params + batch
        return new_p, opt_state + 1, {"loss": jnp.sum(new_p)}

    return StepBundle(cfg=None, shape=None, mesh=None, rules=None,
                      model=None, step_fn=toy_step, in_shardings=None,
                      out_shardings=None, input_sds=()), toy_step


def _toy_split_bundle():
    """A toy bundle with the grad/apply phase split pipelined_steps
    needs (mirrors build_train_step's two ST queues)."""
    import jax.numpy as jnp

    from repro.launch.steps import StepBundle

    def toy_grad(params, batch):
        return batch * 2.0, {"loss": jnp.sum(params)}

    def toy_apply(params, opt_state, grads):
        return params - 0.1 * grads, opt_state + 1, {"gnorm": jnp.sum(grads)}

    def toy_step(p, o, b):
        g, m = toy_grad(p, b)
        p, o, om = toy_apply(p, o, g)
        return p, o, {**m, **om}

    bundle = StepBundle(cfg=None, shape=None, mesh=None, rules=None,
                        model=None, step_fn=toy_step, in_shardings=None,
                        out_shardings=None, input_sds=(),
                        grad_fn=toy_grad, apply_fn=toy_apply)
    return bundle, toy_grad, toy_apply


def test_persistent_steps_validates_and_wraps():
    """Fast checks: n_iters guard + the fori_loop wrap itself, on a toy
    StepBundle (no model compile) — N wrapped steps == N sequential,
    with the full per-step metrics trace in the stacked carry."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import persistent_steps

    bundle, toy_step = _toy_bundle()

    with pytest.raises(ValueError):
        persistent_steps(bundle, 0)

    wrapped = persistent_steps(bundle, 3)
    assert wrapped is not bundle and wrapped.model is bundle.model
    p0, o0, b = jnp.zeros(4), jnp.int32(0), jnp.ones(4)
    pN, oN, met = jax.jit(wrapped.step_fn)(p0, o0, b)
    p, o = p0, o0
    want_losses = []
    for _ in range(3):
        p, o, want = toy_step(p, o, b)
        want_losses.append(float(want["loss"]))
    np.testing.assert_allclose(np.asarray(pN), np.asarray(p))
    assert int(oN) == int(o) == 3
    # stacked per-step metrics + realized count, not last-step-only
    assert met["loss"].shape == (3,)
    np.testing.assert_allclose(np.asarray(met["loss"]), want_losses)
    assert int(met["steps_done"]) == 3

    p1, o1, met1 = persistent_steps(bundle, 1).step_fn(p0, o0, b)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0 + b))
    assert met1["loss"].shape == (1,) and int(met1["steps_done"]) == 1


def test_persistent_steps_indexes_stacked_batch():
    """Regression: a stacked batch (leading n_iters axis) feeds one
    slice per inner step — not the identical batch every step."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import persistent_steps

    bundle, toy_step = _toy_bundle()
    wrapped = persistent_steps(bundle, 3)
    p0, o0 = jnp.zeros(4), jnp.int32(0)
    stacked = jnp.stack([jnp.full(4, 1.0), jnp.full(4, 2.0),
                         jnp.full(4, 3.0)])

    pN, oN, met = jax.jit(wrapped.step_fn)(p0, o0, stacked)
    p, o = p0, o0
    want_losses = []
    for j in range(3):
        p, o, want = toy_step(p, o, stacked[j])
        want_losses.append(float(want["loss"]))
    np.testing.assert_allclose(np.asarray(pN), np.asarray(p))  # 1+2+3 = 6
    np.testing.assert_allclose(np.asarray(met["loss"]), want_losses)

    # explicit override forces the broadcast interpretation
    forced = persistent_steps(bundle, 4, stacked=False)
    pB, _, _ = jax.jit(forced.step_fn)(p0, o0, jnp.ones(4))
    np.testing.assert_allclose(np.asarray(pB), 4.0)


def test_persistent_steps_until_plateau():
    """loss_plateau until= stops the device loop early and reports the
    realized step count (metrics zero-padded past it)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import loss_plateau, persistent_steps

    bundle, _ = _toy_bundle()
    wrapped = persistent_steps(bundle, 6, until=loss_plateau(1e-6))
    p0, o0 = jnp.zeros(4), jnp.int32(0)
    # steps 1-2 move the loss; batches 3+ are zero -> plateau
    stacked = jnp.stack([jnp.ones(4), jnp.ones(4)] + [jnp.zeros(4)] * 4)
    pN, oN, met = jax.jit(wrapped.step_fn)(p0, o0, stacked)
    done = int(met["steps_done"])
    assert done == 3  # first flat delta observed after step 3
    assert int(oN) == done
    np.testing.assert_allclose(np.asarray(met["loss"]),
                               [4.0, 8.0, 8.0, 0.0, 0.0, 0.0])

    # an always-true predicate runs to the n_iters bound
    full = persistent_steps(bundle, 4, until=lambda m, i: jnp.asarray(True),
                            stacked=False)
    _, oF, metF = jax.jit(full.step_fn)(p0, o0, jnp.ones(4))
    assert int(metF["steps_done"]) == 4 and int(oF) == 4


def test_pipelined_steps_matches_staleness1_reference():
    """pipelined_steps overlaps apply(i-1) with grad(i): the realized
    schedule is the classic staleness-1 pipeline, checked against a
    hand-rolled python reference."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import pipelined_steps

    bundle, toy_grad, toy_apply = _toy_split_bundle()
    n = 4
    stacked = jnp.stack([jnp.full(3, float(i + 1)) for i in range(n)])
    wrapped = pipelined_steps(bundle, n)
    assert wrapped is not bundle
    pN, oN, met = jax.jit(wrapped.step_fn)(jnp.zeros(3), jnp.int32(0),
                                           stacked)

    # reference: same software-pipelined schedule, sequentially
    p, o = jnp.zeros(3), 0
    g_prev, m = toy_grad(p, stacked[0])
    losses, gnorms = [float(m["loss"])], []
    for i in range(1, n):
        g_i, m = toy_grad(p, stacked[i])        # pre-apply params
        p, o, om = toy_apply(p, o, g_prev)      # apply step i-1
        losses.append(float(m["loss"]))
        gnorms.append(float(om["gnorm"]))
        g_prev = g_i
    p, o, om = toy_apply(p, o, g_prev)          # drain
    gnorms.append(float(om["gnorm"]))

    np.testing.assert_allclose(np.asarray(pN), np.asarray(p))
    assert int(oN) == n and int(met["steps_done"]) == n
    # slot i: step i's grad metrics AND step i's own apply metrics
    np.testing.assert_allclose(np.asarray(met["loss"]), losses)
    np.testing.assert_allclose(np.asarray(met["gnorm"]), gnorms)


def test_pipelined_steps_single_step_is_sequential():
    """n_iters=1 degenerates to the exact sequential step (no
    staleness: grad then apply)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import pipelined_steps

    bundle, _, _ = _toy_split_bundle()
    p0, o0, b = jnp.ones(3), jnp.int32(0), jnp.full(3, 2.0)
    p1, o1, met1 = jax.jit(pipelined_steps(bundle, 1).step_fn)(p0, o0, b)
    ps, os_, mets = bundle.step_fn(p0, o0, b)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(ps))
    assert int(o1) == int(os_) == 1
    np.testing.assert_allclose(float(met1["loss"][0]), float(mets["loss"]))
    np.testing.assert_allclose(float(met1["gnorm"][0]), float(mets["gnorm"]))


def test_pipelined_steps_validates():
    import jax.numpy as jnp

    from repro.launch.steps import pipelined_steps

    split_bundle, _, _ = _toy_split_bundle()
    with pytest.raises(ValueError, match="n_iters"):
        pipelined_steps(split_bundle, 0)
    # a bundle without the grad/apply split (e.g. serve) is rejected
    plain_bundle, _ = _toy_bundle()
    with pytest.raises(ValueError, match="grad/apply"):
        pipelined_steps(plain_bundle, 2)
    # colliding metric keys between the two phases are rejected
    bad, _, _ = _toy_split_bundle()
    bad.apply_fn = lambda p, o, g: (p, o, {"loss": jnp.sum(g)})
    with pytest.raises(ValueError, match="collide"):
        bad = pipelined_steps(bad, 2)
        bad.step_fn(jnp.zeros(3), jnp.int32(0), jnp.ones(3))


def test_build_pipelined_train_step_on_real_model():
    """The real-model pipeline: staleness-1 schedule against an explicit
    two-phase python loop using the bundle's own grad/apply split."""
    import jax

    from repro.configs.base import ModelConfig, ShapeConfig
    from repro.launch.steps import build_pipelined_train_step, build_train_step
    from repro.optim import AdamWConfig, adamw_init
    from repro.parallel import make_mesh

    cfg = ModelConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=64, remat="none",
                      scan_layers=False)
    shape = ShapeConfig("t", 16, 2, "train")
    mesh = make_mesh((1,), ("data",))
    opt = AdamWConfig(lr=1e-3)
    n = 3

    b1 = build_train_step(cfg, shape, mesh, opt=opt)
    assert b1.grad_fn is not None and b1.apply_fn is not None
    bN = build_pipelined_train_step(cfg, shape, mesh, n_iters=n, opt=opt,
                                    stacked=False)
    params, _ = b1.model.init(jax.random.PRNGKey(0))
    opt_state = adamw_init(params, opt)
    from repro.data.synthetic import SyntheticTokens
    batch = {k: jax.numpy.asarray(v)
             for k, v in SyntheticTokens(cfg, shape).batch(0).items()}

    with mesh:
        pN, oN, metN = jax.jit(bN.step_fn)(params, opt_state, batch)
        # reference: the same pipeline, phase by phase on the host
        p, o = params, opt_state
        g_prev, m = b1.grad_fn(p, batch)
        losses = [float(m["loss"])]
        for _ in range(1, n):
            g_i, m = b1.grad_fn(p, batch)
            p, o, _ = b1.apply_fn(p, o, g_prev)
            losses.append(float(m["loss"]))
            g_prev = g_i
        p, o, _ = b1.apply_fn(p, o, g_prev)

    assert int(metN["steps_done"]) == n and int(oN["step"]) == n
    np.testing.assert_allclose(np.asarray(metN["loss"], np.float64), losses,
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(pN)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-3, atol=2e-3)


def test_train_rejects_plateau_without_inner_steps():
    """plateau_eps can only fire inside a multi-step device loop; a
    silent no-op config is rejected up front."""
    from repro.configs.base import ModelConfig, ShapeConfig
    from repro.launch.train import train
    from repro.parallel import make_mesh

    cfg = ModelConfig(name="tiny")
    shape = ShapeConfig("t", 16, 2, "train")
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="inner_steps"):
        train(cfg, shape, mesh, steps=2, plateau_eps=1e-4)
    with pytest.raises(ValueError, match="inner_steps"):
        train(cfg, shape, mesh, steps=2, inner_steps=0)


def test_train_resume_restores_opt_state(tmp_path):
    """Regression: an interrupted+resumed run must match an
    uninterrupted one bit-for-bit — AdamW moments and the LR-schedule
    position live in the checkpoint, not just params."""
    import jax

    from repro.configs.base import ModelConfig, ShapeConfig
    from repro.launch.train import train
    from repro.optim import AdamWConfig
    from repro.parallel import make_mesh

    cfg = ModelConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=64, remat="none",
                      scan_layers=False)
    shape = ShapeConfig("t", 16, 2, "train")
    mesh = make_mesh((1,), ("data",))
    opt = AdamWConfig(lr=1e-3)
    da, db = str(tmp_path / "a"), str(tmp_path / "b")

    pa, oa, _ = train(cfg, shape, mesh, steps=4, opt=opt, checkpoint_dir=da,
                      checkpoint_every=2, log_every=100)
    # interrupt at step 2, then resume to 4 from the checkpoint
    train(cfg, shape, mesh, steps=2, opt=opt, checkpoint_dir=db,
          checkpoint_every=2, log_every=100)
    pb, ob, _ = train(cfg, shape, mesh, steps=4, opt=opt, checkpoint_dir=db,
                      checkpoint_every=2, log_every=100)

    assert int(oa["step"]) == int(ob["step"]) == 4  # schedule position kept
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(oa), jax.tree.leaves(ob)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_train_inner_steps_matches_per_step(subproc):
    """train(inner_steps=N) — stacked real batches, one dispatch per N
    steps — reproduces the per-step driver's loss trace and params."""
    r = subproc("""
import numpy as np, jax
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.train import train
from repro.optim import AdamWConfig
from repro.parallel import make_mesh

cfg = ModelConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=64, vocab=64, remat="none",
                  scan_layers=False)
shape = ShapeConfig("t", 16, 2, "train")
mesh = make_mesh((1,), ("data",))
opt = AdamWConfig(lr=1e-3)
p1, o1, h1 = train(cfg, shape, mesh, steps=6, opt=opt, log_every=1)
pN, oN, hN = train(cfg, shape, mesh, steps=6, opt=opt, log_every=1,
                   inner_steps=3)
np.testing.assert_allclose([m["loss"] for m in h1],
                           [m["loss"] for m in hN], rtol=1e-5)
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(pN)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=1e-5, atol=1e-6)
print("inner steps ok")
""", devices=1)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "inner steps ok" in r.stdout


# -- persistent compile cache of the entry points ------------------------------


def test_compile_cache_leaves_an_env_dir_to_jax(monkeypatch):
    import jax
    from repro.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    assert enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path  # same path every run
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
