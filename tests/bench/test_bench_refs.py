"""The benchmark's plain references against witnesses of the program's
own, and the controls (the reference one precision lower) failing."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import harness, weights
from bench.refs import faces as faces_ref
from bench.refs import qwen as qwen_ref


@pytest.mark.parametrize("grid", [(1, 1, 1), (2, 2, 1)])
@pytest.mark.parametrize("damping", [0.0, 0.03])
def test_faces_reference_matches_the_numpy_oracle(grid, damping):
    from repro.core.halo import FacesConfig, faces_oracle

    cfg = FacesConfig(grid=grid, points=(6, 5, 4), periodic=True,
                      damping=damping)
    u0 = np.random.default_rng(0).standard_normal(
        (*grid, *cfg.points)).astype(np.float32)
    want = faces_oracle(faces_oracle(u0, cfg), cfg)
    got, res = faces_ref.run(jnp.asarray(u0), n_iters=2, damping=damping)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    assert np.isclose(float(res[-1]), np.sqrt(np.mean(want ** 2)), rtol=1e-5)


def test_qwen_reference_matches_the_program_forward():
    from bench.drivers.serve_continuous import model_config
    from repro.models import Model

    cfg = dict(tiny.QWEN, torch_dtype="float32")
    model = Model(model_config(cfg))
    sds, _ = model.abstract_init()
    w = weights.make(sds, 5)
    tokens = np.random.default_rng(1).integers(0, cfg["vocab_size"], 24)
    want = np.asarray(model.forward_logits(w, {"tokens": jnp.asarray(
        tokens[None], jnp.int32)})[0], np.float64)
    best, at, arg = qwen_ref.scores(w, cfg, tokens, np.roll(tokens, -1))
    np.testing.assert_allclose(best, want.max(-1), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(arg, want.argmax(-1))
    np.testing.assert_allclose(
        at, want[np.arange(24), np.roll(tokens, -1)], rtol=1e-4, atol=1e-4)


def _faces_control(monkeypatch):
    """Put the bfloat16 reference in the program's place."""
    from repro.core.engine_persistent import PersistentEngine

    def control(self, mem):
        self.stats.dispatches += 1
        u, r = faces_ref.run(mem["u"], n_iters=self.n_iters, damping=0.03,
                             dtype=jnp.bfloat16)
        return dict(mem, u=u.astype(jnp.float32)), r.astype(jnp.float32)

    monkeypatch.setattr(PersistentEngine, "__call__", control)


def _run(root, workload, seed=7):
    import time

    return harness.run_cell(root, workload, seed, 0.3, False,
                            jax.devices()[:1], t_start=time.perf_counter(),
                            log=lambda msg: None)


def test_faces_control_is_not_correct(monkeypatch):
    root = tiny.make_root(tempfile.mkdtemp())
    assert _run(root, "faces")["correct"]
    _faces_control(monkeypatch)
    line = _run(root, "faces")
    assert not line["correct"]
    assert line["checks"]["field_rel_err"]["value"] > 1e-3


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_reads_above_the_limit(seed):
    """At each served position, the token the float8 control puts first
    lies further below the reference's best than the limit allows, while
    the program's served tokens do not."""
    root = tiny.make_root(tempfile.mkdtemp())
    _, d = _driver(root, seed)
    d.setup()
    d.window(None)
    limit = tiny.SERVE_MIX["limits"]["logit_gap"]
    program, control = [], []
    for i, tokens in d.sample():
        args = (d.params, d.ctx.config, d.prompts[i], tokens)
        program.append(qwen_ref.served_gap(*args))
        control.append(qwen_ref.served_gap(*args, quant=True))
    assert max(program) <= limit < max(control)


def _greedy(w, prompt, n, quant):
    """``n`` greedy tokens after ``prompt`` from the reference.  The
    sequence is padded to its final length, which causal attention does
    not see, so every step runs the same shape."""
    P = len(prompt)
    seq = np.zeros(P + n, np.int32)
    seq[:P] = prompt
    for t in range(n):
        _, _, arg = qwen_ref.scores(w, tiny.QWEN, seq, seq, quant=quant)
        seq[P + t] = arg[P - 1 + t]
    return seq[P:]


def _serving_control(monkeypatch):
    """Put the float8 reference in the program's place: the window's
    requests are served by the program, and each then carries the
    tokens that the control decodes greedily for its prompt."""
    import dataclasses
    import importlib

    serve = importlib.import_module("repro.launch.serve")
    real = serve.serve_continuous

    def control(cfg, mesh, **kw):
        results, stats = real(cfg, mesh, **kw)
        if kw["arrival_rate"] > 0:      # the window, not the warm-up
            prompts = kw["prompts"]["tokens"]
            results = [dataclasses.replace(r, tokens=_greedy(
                kw["params"], prompts[r.rid], len(r.tokens), quant=True))
                for r in results]
        return results, stats

    monkeypatch.setattr(serve, "serve_continuous", control)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_is_not_correct(monkeypatch, seed):
    """The run's own check fails when the served tokens are the float8
    control's greedy choices."""
    root = tiny.make_root(tempfile.mkdtemp())
    _serving_control(monkeypatch)
    line = _run(root, "serve", seed)
    assert not line["correct"]
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _driver(root, seed):
    from bench.tools import limits

    old = limits.ROOT
    limits.ROOT = root
    try:
        return limits._driver("serve", seed, 1.0)
    finally:
        limits.ROOT = old
