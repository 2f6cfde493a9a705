"""The program's own observability: named scopes on what the engines
lower, host spans while a profiler runs, and the serving counters and
request stamps.

Scopes add HLO ``op_name`` metadata and nothing else: the compiled
computation with its metadata stripped, the Faces field and residuals,
and the served tokens are the same as with every scope taken out.
"""

import contextlib
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import FacesConfig, PersistentEngine, build_faces_program
from repro.core.halo import AXES3, global_residual_fn
from repro.launch.serve import ServeEngine, serve_continuous, synthetic_batch
from repro.parallel import make_mesh

PROMPT, GEN, SLOTS, CHUNK, N_REQ = 8, 6, 2, 3, 5


def _op_names(text):
    """The scopes in the ``op_name`` metadata of HLO text: every path
    component but the last, which names the op (or an argument)."""
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")[:-1]}


def _computation(text):
    """Compiled HLO text without metadata or the stack-frame tables."""
    text = text.split("\nFileNames")[0]
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@contextlib.contextmanager
def _unscoped():
    """Trace with every ``jax.named_scope`` a no-op."""
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = real


# -- Faces ---------------------------------------------------------------


FACES = FacesConfig(grid=(1, 1, 1), points=(4, 3, 5), periodic=True,
                    damping=0.03)


def _faces(mode):
    """(compiled text, field, residuals) of 3 persistent iterations."""
    mesh = make_mesh((1, 1, 1), AXES3)
    prog = build_faces_program(FACES, mesh).persistent(3)
    eng = PersistentEngine(prog, mode=mode,
                           reduce_fn=global_residual_fn(FACES))
    text = eng.lower().compile().as_text()
    u0 = np.random.RandomState(0).randn(*FACES.grid, *FACES.points)
    mem, red = eng(eng.init_buffers({"u": u0.astype(np.float32)}))
    return text, np.asarray(mem["u"]), np.asarray(red)


@pytest.fixture(scope="module", params=["stream", "dataflow"])
def faces_runs(request):
    scoped = _faces(request.param)
    with _unscoped():
        plain = _faces(request.param)
    return scoped, plain


@pytest.mark.parametrize("scope", ["pack0", "unpack0", "interior", "damp",
                                   "residual", "exchange"])
def test_faces_ops_carry_their_queue_op_scope(faces_runs, scope):
    (text, _, _), (plain, _, _) = faces_runs
    assert scope in _op_names(text)
    assert scope not in _op_names(plain)


def test_faces_scopes_change_no_op_and_no_bit(faces_runs):
    (text, u, red), (plain, u_plain, red_plain) = faces_runs
    assert _computation(text) == _computation(plain)
    np.testing.assert_array_equal(u, u_plain)
    np.testing.assert_array_equal(red, red_plain)


# -- serving ---------------------------------------------------------------


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen1.5-0.5b").smoke()


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _engine(cfg, mesh):
    return ServeEngine(cfg, mesh, slots=SLOTS, prompt_len=PROMPT,
                       max_new=GEN, chunk=CHUNK, eos_id=-1)


@pytest.fixture(scope="module")
def params(cfg, mesh):
    eng = _engine(cfg, mesh)
    with mesh:
        p, _ = eng.model.init(jax.random.PRNGKey(0))
        return jax.device_put(p, eng.pre.in_shardings[0])


@pytest.fixture(scope="module")
def prompts(cfg):
    return synthetic_batch(cfg, np.random.RandomState(1), N_REQ, PROMPT)


def _serve(cfg, mesh, params, prompts, rate=0.0):
    eng = _engine(cfg, mesh)
    results, stats = serve_continuous(
        cfg, mesh, slots=SLOTS, prompt_len=PROMPT, max_new=GEN,
        n_requests=N_REQ, chunk=CHUNK, arrival_rate=rate, seed=0,
        params=params, prompts=prompts, engine=eng)
    return eng, results, stats


def _program_texts(eng, params):
    """Compiled text of the admission and the decode programs."""
    caches, tok, active, rem = eng.init_state()
    batch_in = synthetic_batch(eng.cfg, np.random.RandomState(0), SLOTS,
                               PROMPT)
    admit = np.ones(SLOTS, bool)
    new_rem = np.full(SLOTS, GEN, np.int32)
    with eng.mesh:
        admit_text = eng.admit_decode._fn.lower(
            params, caches, tok, active, rem, batch_in, admit,
            new_rem).compile().as_text()
        decode_text = eng.decode._fn.lower(
            params, caches, tok, active, rem).compile().as_text()
    return {"admit_decode": admit_text, "decode": decode_text}


@pytest.fixture(scope="module")
def serve_texts(cfg, mesh, params):
    scoped = _program_texts(_engine(cfg, mesh), params)
    with _unscoped():
        plain = _program_texts(_engine(cfg, mesh), params)
    return scoped, plain


@pytest.mark.parametrize("program,scope", [("admit_decode", "admit"),
                                           ("admit_decode", "decode"),
                                           ("decode", "decode")])
def test_serving_ops_carry_their_scope(serve_texts, program, scope):
    scoped, plain = serve_texts
    assert scope in _op_names(scoped[program])
    assert scope not in _op_names(plain[program])


@pytest.mark.parametrize("program", ["admit_decode", "decode"])
def test_serving_scopes_change_no_op(serve_texts, program):
    scoped, plain = serve_texts
    assert _computation(scoped[program]) == _computation(plain[program])


@pytest.fixture(scope="module")
def served(cfg, mesh, params, prompts):
    return _serve(cfg, mesh, params, prompts)


def test_served_tokens_are_bit_identical_without_scopes(
        cfg, mesh, params, prompts, served):
    _, results, _ = served
    with _unscoped():
        _, plain, _ = _serve(cfg, mesh, params, prompts)
    for r, p in zip(results, plain):
        np.testing.assert_array_equal(r.tokens, p.tokens)


# -- counters and request stamps -------------------------------------------


@pytest.fixture(scope="module", params=[0.0, 40.0], ids=["burst", "poisson"])
def counted(request, cfg, mesh, params, prompts):
    return _serve(cfg, mesh, params, prompts, rate=request.param)


def test_request_stamps_are_ordered(counted):
    _, results, _ = counted
    for r in results:
        assert r.t_arrive <= r.t_admit <= r.t_first <= r.t_done


def test_serve_stats_count_the_rounds(counted):
    eng, results, stats = counted
    st = eng.stats
    assert st.admitted == N_REQ
    assert st.prefill_rows == SLOTS * st.admit_decode
    assert st.decoded + st.admitted == stats["total_tokens"]
    assert st.rounds == st.sync_points == st.admit_decode + st.decode
    # each round decodes for at least one step, at most a chunk's worth
    assert st.rounds - st.admit_decode <= st.steps <= CHUNK * st.rounds
    assert st.decoded <= SLOTS * st.steps


def test_stats_dict_is_the_calls_difference(counted):
    eng, _, stats = counted
    assert stats["dispatches"] == eng.stats.dispatches
    assert stats["admit_dispatches"] == eng.admit_decode.calls
    assert stats["decode_dispatches"] == eng.decode.calls
    assert stats["sync_points"] == eng.stats.sync_points


# -- host spans while a profiler runs ---------------------------------------


def _program_spans(log_dir):
    """The ``st.*`` host spans of a profiler file, with their arguments."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [(ev.name, dict(ev.stats)) for ev in line.events
                    if ev.name.startswith("st.")]
    return out


@pytest.mark.parametrize("points,stencil", [((4, 3, 5), "jnp"),
                                            ((8, 8, 128), "pallas")])
def test_dispatch_span_names_the_stencil_lowering(tmp_path, points, stencil):
    cfg = dataclasses.replace(FACES, points=points)
    faces = PersistentEngine(
        build_faces_program(cfg, make_mesh((1, 1, 1), AXES3)).persistent(2))
    mem = faces.init_buffers()
    faces(mem)          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(faces(mem))
    finally:
        jax.profiler.stop_trace()
    assert [a["stencil"] for n, a in _program_spans(str(tmp_path))
            if n == "st.persistent.dispatch"] == [stencil]


def test_spans_record_while_a_profiler_runs(tmp_path, cfg, mesh, params,
                                            prompts):
    mesh3 = make_mesh((1, 1, 1), AXES3)
    faces = PersistentEngine(build_faces_program(FACES, mesh3).persistent(3),
                             reduce_fn=global_residual_fn(FACES))
    mem = faces.init_buffers()
    faces(mem)          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(faces(mem))
        eng, _, _ = _serve(cfg, mesh, params, prompts)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(str(tmp_path))
    names = [n for n, _ in spans]
    assert [a for n, a in spans if n == "st.persistent.dispatch"] == [
        {"iters": 3, "stencil": "jnp"}]
    rounds = [a for n, a in spans if n == "st.serve.round"]
    st = eng.stats
    assert len(rounds) == st.rounds == names.count("st.serve.emit")
    assert names.count("st.serve.admit_prep") == st.admit_decode
    for key in ("admitted", "decoded", "steps"):
        assert sum(a[key] for a in rounds) == getattr(st, key)
