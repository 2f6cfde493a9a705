"""core/overlap.py + core/collectives.py — decomposed and ST-expressed
collectives vs their jax.lax references.

Fast lane: single-device trivial paths (axis size 1 short-circuits),
the `triggered` ST wrapper, and the ST collective-matmul builders at
n=1.  Slow lane: per-collective subprocess tests on an 8-device mesh
(finer-grained than the combined check in tests/test_distributed.py,
so a regression names the exact collective), plus bit-identity
properties of the ST programs — across dtypes, uneven (non-square,
non-power-of-two) tiles, bidirectional rings, and the chained
transformer block as one persistent dispatch.
"""

import numpy as np
import pytest


def _smap1(f, in_specs, out_specs):
    import jax
    from repro.parallel import make_mesh

    mesh = make_mesh((1,), ("x",))
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


# -- trivial paths (fast, single device) --------------------------------------


def test_single_device_paths_are_identity():
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.core import overlap

    x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
    for fn in (
        partial(overlap.all_gather_ring, axis="x"),
        partial(overlap.all_gather_ring, axis="x", bidirectional=False),
        partial(overlap.reduce_scatter_ring, axis="x"),
        partial(overlap.all_to_all_ppermute, axis="x"),
    ):
        got = _smap1(fn, (P("x"),), P("x"))(x)
        np.testing.assert_allclose(np.asarray(got), x, rtol=1e-6)


def test_all_gather_matmul_single_device():
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from repro.core import overlap

    rng = np.random.RandomState(1)
    x = rng.randn(8, 4).astype(np.float32)
    w = rng.randn(4, 3).astype(np.float32)
    got = _smap1(partial(overlap.all_gather_matmul, axis="x"),
                 (P("x"), P()), P("x"))(x, w)
    np.testing.assert_allclose(np.asarray(got), x @ w, rtol=1e-5, atol=1e-5)


def test_triggered_wrapper_preserves_values():
    import jax.numpy as jnp

    from repro.core import fresh_token, overlap

    token = fresh_token()
    fn = overlap.triggered(lambda v: v * 2.0, token)
    out = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


def test_collective_builders_single_device():
    # n=1 degenerate ring: the ST programs reduce to their local math
    from repro.core import collectives
    from repro.core.engine_fused import FusedEngine
    from repro.parallel import make_mesh

    mesh = make_mesh((1,), ("x",))
    rng = np.random.RandomState(0)
    x = rng.randn(8, 4).astype(np.float32)
    w = rng.randn(4, 3).astype(np.float32)
    for cm, inputs, want in (
        (collectives.build_all_gather_matmul(mesh, "x", 8, 4, 3),
         {"x": x, "w": w}, x @ w),
        (collectives.build_matmul_reduce_scatter(mesh, "x", 8, 4, 3),
         {"x": x, "w": w}, x @ w),
        (collectives.build_all_to_all(mesh, "x", 8, 4), {"x": x}, x),
    ):
        eng = FusedEngine(cm.program, mode="dataflow")
        got = np.asarray(eng(eng.init_buffers(inputs))[cm.output])
        np.testing.assert_array_equal(
            got, np.asarray(cm.reference(*(inputs[b] for b in cm.inputs))))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert eng.stats.dispatches == 1


def test_tp_block_single_device():
    from repro.core import collectives
    from repro.core.engine_persistent import PersistentEngine
    from repro.parallel import make_mesh

    mesh = make_mesh((1,), ("x",))
    rng = np.random.RandomState(1)
    x = rng.randn(6, 4).astype(np.float32)
    w1 = rng.randn(4, 5).astype(np.float32)
    w2 = rng.randn(5, 4).astype(np.float32)
    tp = collectives.build_tp_block(mesh, "x", 6, 4, 5, chain=True)
    eng = PersistentEngine(tp.program.persistent(3), donate=True)
    got = np.asarray(eng(eng.init_buffers(
        {"x": x, "w1": w1, "w2": w2}))["out"])
    ref = x
    for _ in range(3):
        ref = np.maximum(ref @ w1, 0.0) @ w2
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    assert eng.stats.dispatches == 1


def test_moe_dispatch_builder_builds_and_runs():
    from repro.core import collectives
    from repro.core.engine_fused import FusedEngine
    from repro.models.moe import build_moe_dispatch_program
    from repro.parallel import make_mesh

    mesh = make_mesh((1,), ("x",))
    cm = build_moe_dispatch_program(mesh, "x", n_experts=2, capacity=3,
                                    d_model=4)
    assert isinstance(cm, collectives.CollectiveMatmul)
    assert cm.inputs == ("x",)
    x = np.random.RandomState(2).randn(6, 4).astype(np.float32)
    eng = FusedEngine(cm.program, mode="dataflow")
    got = np.asarray(eng(eng.init_buffers({"x": x}))[cm.output])
    np.testing.assert_array_equal(got, x)  # n=1 dispatch is the identity


# -- 8-device references (subprocess, slow lane) ------------------------------

_PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from repro.core import overlap
from repro.parallel import make_mesh
from jax.sharding import PartitionSpec as P
mesh = make_mesh((8,), ("x",))
def smap(f, in_specs, out_specs):
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))
"""


def _check(subproc, code, prelude=None):
    r = subproc((prelude if prelude is not None else _PRELUDE) + code)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


@pytest.mark.slow
@pytest.mark.parametrize("bidirectional", [False, True])
def test_all_gather_ring_matches_lax(subproc, bidirectional):
    _check(subproc, f"""
x = np.random.RandomState(0).randn(32, 16).astype(np.float32)
got = smap(partial(overlap.all_gather_ring, axis="x",
                   bidirectional={bidirectional}), (P("x"),), P())(x)
want = smap(lambda v: jax.lax.all_gather(v, "x", axis=0, tiled=True),
            (P("x"),), P())(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
np.testing.assert_allclose(np.asarray(got), x, rtol=1e-6)
""")


@pytest.mark.slow
def test_reduce_scatter_ring_matches_lax(subproc):
    _check(subproc, """
x = np.random.RandomState(1).randn(32, 16).astype(np.float32)
got = smap(partial(overlap.reduce_scatter_ring, axis="x"),
           (P(None, None),), P("x"))(x)
want = smap(lambda v: jax.lax.psum_scatter(v, "x", scatter_dimension=0,
                                           tiled=True),
            (P(None, None),), P("x"))(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                           atol=1e-5)
""")


@pytest.mark.slow
def test_all_to_all_ppermute_matches_lax(subproc):
    _check(subproc, """
x = np.random.RandomState(2).randn(64, 4).astype(np.float32)
got = smap(partial(overlap.all_to_all_ppermute, axis="x"),
           (P("x"),), P("x"))(x)
want = smap(lambda v: jax.lax.all_to_all(v, "x", split_axis=0,
                                         concat_axis=0, tiled=True),
            (P("x"),), P("x"))(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
""")


# -- ST-expressed collectives, 8-device bit-identity (slow lane) --------------

_ST_PRELUDE = _PRELUDE + """
from repro.core import collectives
from repro.core.engine_fused import FusedEngine
from repro.core.engine_persistent import PersistentEngine

def run_st(cm, inputs):
    eng = FusedEngine(cm.program, mode="dataflow")
    out = np.asarray(eng(eng.init_buffers(inputs))[cm.output])
    assert eng.stats.dispatches == 1, eng.stats.dispatches
    return out
"""


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bidirectional", [False, True])
def test_st_all_gather_matmul_bit_identical(subproc, dtype, bidirectional):
    # uneven (non-square, non-power-of-two) tiles on purpose: m=24 is
    # 3 rows per rank, k=7 / f=5 share no factor with the ring size
    _check(subproc, prelude=_ST_PRELUDE, code=f"""
dt = jnp.{dtype}
cm = collectives.build_all_gather_matmul(mesh, "x", 24, 7, 5, dt,
                                         bidirectional={bidirectional})
rng = np.random.RandomState(0)
inputs = {{"x": rng.randn(24, 7).astype(dt),
           "w": rng.randn(7, 5).astype(dt)}}
got = run_st(cm, inputs)
ref = np.asarray(cm.reference(inputs["x"], inputs["w"]))
stock = np.asarray(cm.reference_stock(inputs["x"], inputs["w"]))
np.testing.assert_array_equal(got, ref)
np.testing.assert_array_equal(got, stock)  # pure gather: stock bitwise too
""")


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_st_matmul_reduce_scatter_bit_identical(subproc, dtype):
    _check(subproc, prelude=_ST_PRELUDE, code=f"""
dt = jnp.{dtype}
cm = collectives.build_matmul_reduce_scatter(mesh, "x", 24, 16, 5, dt)
rng = np.random.RandomState(1)
inputs = {{"x": rng.randn(24, 16).astype(dt),
           "w": rng.randn(16, 5).astype(dt)}}
got = run_st(cm, inputs)
ref = np.asarray(cm.reference(inputs["x"], inputs["w"]))
np.testing.assert_array_equal(got, ref)  # same ring accumulate order
# psum_scatter sums in a different order: allclose only
stock = np.asarray(cm.reference_stock(inputs["x"], inputs["w"]))
tol = 1e-5 if dt == jnp.float32 else 1e-1
np.testing.assert_allclose(got.astype(np.float32),
                           stock.astype(np.float32), rtol=tol, atol=tol)
""")


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_st_all_to_all_bit_identical(subproc, dtype):
    _check(subproc, prelude=_ST_PRELUDE, code=f"""
dt = jnp.{dtype}
cm = collectives.build_all_to_all(mesh, "x", 128, 3, dt)
x = np.random.RandomState(2).randn(128, 3).astype(dt)
got = run_st(cm, {{"x": x}})
np.testing.assert_array_equal(got, np.asarray(cm.reference(x)))
np.testing.assert_array_equal(got, np.asarray(cm.reference_stock(x)))
""")


@pytest.mark.slow
def test_st_tp_block_chain_8dev(subproc):
    # the headline row at test scale: N chained blocks, ONE dispatch,
    # bitwise vs the decomposed chain, allclose vs the stock lowering
    _check(subproc, prelude=_ST_PRELUDE, code="""
N = 3
tp = collectives.build_tp_block(mesh, "x", 32, 8, 16, chain=True)
rng = np.random.RandomState(3)
x0 = rng.randn(32, 8).astype(np.float32)
w1 = rng.randn(8, 16).astype(np.float32)
w2 = rng.randn(16, 8).astype(np.float32)
eng = PersistentEngine(tp.program.persistent(N), donate=True)
got = np.asarray(eng(eng.init_buffers(
    {"x": x0, "w1": w1, "w2": w2}))["out"])
assert eng.stats.dispatches == 1, eng.stats.dispatches
ref = stock = x0
for _ in range(N):
    ref = tp.reference(ref, w1, w2)
    stock = tp.reference_stock(stock, w1, w2)
np.testing.assert_array_equal(got, np.asarray(ref))
np.testing.assert_allclose(got, np.asarray(stock), rtol=1e-4, atol=1e-5)
""")


@pytest.mark.slow
def test_st_builders_reject_uneven_tiles(subproc):
    _check(subproc, prelude=_ST_PRELUDE, code="""
from repro.core.queue import QueueError
from repro.models.moe import build_moe_dispatch_program
for bad in (
    lambda: collectives.build_all_gather_matmul(mesh, "x", 20, 4, 4),
    lambda: collectives.build_matmul_reduce_scatter(mesh, "x", 20, 4, 4),
    lambda: collectives.build_all_to_all(mesh, "x", 96, 4),  # % 64 != 0
    lambda: build_moe_dispatch_program(mesh, "x", 3, 2, 4),
):
    try:
        bad()
    except (QueueError, ValueError):
        pass
    else:
        raise AssertionError(f"indivisible shape accepted: {bad}")
""")


@pytest.mark.slow
def test_st_moe_dispatch_matches_lax_8dev(subproc):
    _check(subproc, prelude=_ST_PRELUDE, code="""
from repro.models.moe import build_moe_dispatch_program
cm = build_moe_dispatch_program(mesh, "x", n_experts=8, capacity=2,
                                d_model=3)
x = np.random.RandomState(4).randn(128, 3).astype(np.float32)
got = run_st(cm, {"x": x})
np.testing.assert_array_equal(got, np.asarray(cm.reference_stock(x)))
# the tiled a2a is an involution: the combine leg is the same program
back = run_st(cm, {"x": got})
np.testing.assert_array_equal(back, x)
""")


@pytest.mark.slow
def test_overlapped_matmuls_match_references(subproc):
    _check(subproc, """
rng = np.random.RandomState(3)
x = rng.randn(32, 16).astype(np.float32)
w = rng.randn(16, 8).astype(np.float32)
got = smap(partial(overlap.all_gather_matmul, axis="x"),
           (P("x"), P()), P())(x, w)
np.testing.assert_allclose(np.asarray(got), x @ w, rtol=1e-4, atol=1e-5)

xk = rng.randn(32, 64).astype(np.float32)
wk = rng.randn(64, 8).astype(np.float32)
got = smap(partial(overlap.matmul_reduce_scatter, axis="x"),
           (P(None, "x"), P("x")), P("x"))(xk, wk)
np.testing.assert_allclose(np.asarray(got), xk @ wk, rtol=1e-4, atol=1e-4)
""")
