"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


def rand(rng, shape, dtype):
    x = rng.randn(*shape)
    return jnp.asarray(x, dtype)


# --------------------------------------------------------------------------
# halo pack family
# --------------------------------------------------------------------------

REGION_CASES = [
    ((4, 4, 4), (slice(0, 1), slice(0, 4), slice(0, 4))),      # face
    ((4, 4, 4), (slice(3, 4), slice(0, 1), slice(0, 4))),      # edge
    ((4, 4, 4), (slice(3, 4), slice(3, 4), slice(3, 4))),      # corner
    ((7, 5, 3), (slice(0, 7), slice(4, 5), slice(0, 3))),      # odd sizes
    ((2, 9, 6), (slice(1, 2), slice(0, 9), slice(5, 6))),
    # tile-divisible minor axes: the kernels walk a (sublane, lane)
    # tile-aligned window around the slab, not the whole block
    ((4, 16, 256), (slice(0, 4), slice(0, 16), slice(255, 256))),  # z-face
    ((4, 16, 256), (slice(0, 4), slice(15, 16), slice(0, 256))),   # y-face
    ((4, 16, 256), (slice(3, 4), slice(8, 9), slice(128, 129))),   # corner
]


@pytest.mark.parametrize("shape,region", REGION_CASES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_halo_pack_matches_ref(shape, region, dtype):
    rng = np.random.RandomState(0)
    u = rand(rng, shape, dtype)
    got = ops.halo_pack(u, region)
    want = ref.halo_pack(u, region)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("shape,region", REGION_CASES)
def test_halo_unpack_add_matches_ref(shape, region):
    rng = np.random.RandomState(1)
    u = rand(rng, shape, "float32")
    msg_shape = tuple(s.stop - s.start for s in region)
    msg = rand(rng, msg_shape, "float32")
    np.testing.assert_allclose(
        np.asarray(ops.halo_unpack_add(u, msg, region)),
        np.asarray(ref.halo_unpack_add(u, msg, region)), rtol=1e-6)


# --------------------------------------------------------------------------
# Faces interior stencil
# --------------------------------------------------------------------------


def _rolled_stencil(core):
    """The Faces interior stencil as ``jnp.roll`` expresses it."""
    return core + 0.125 * (
        jnp.roll(core, 1, 0) + jnp.roll(core, -1, 0)
        + jnp.roll(core, 1, 1) + jnp.roll(core, -1, 1)
        + jnp.roll(core, 1, 2) + jnp.roll(core, -1, 2)
        - 6.0 * core
    )


def _assert_ulps(got, want, ulps=2):
    """Equal to ``ulps`` float32 units in the last place of the largest
    value (contraction into fused multiply-adds may round otherwise)."""
    want = np.asarray(want)
    tol = ulps * np.spacing(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)


@pytest.mark.parametrize("shape,block_planes", [
    ((8, 8, 128), None), ((8, 8, 128), 2), ((8, 8, 128), 1),
    ((16, 16, 256), None), ((16, 16, 256), 4),
    ((2, 8, 128), None), ((2, 8, 128), 1),
])
def test_faces_stencil7_matches_ref(shape, block_planes):
    rng = np.random.RandomState(3)
    core = rand(rng, shape, "float32")
    # ghost planes that are not the block's own wrap planes
    lo, hi = (rand(rng, (1,) + shape[1:], "float32") for _ in range(2))
    _assert_ulps(ops.faces_stencil7(core, lo, hi, block_planes=block_planes),
                 ref.faces_stencil7(core, lo, hi))
    # with its own wrap planes as ghosts it is the rolled expression
    _assert_ulps(ops.faces_stencil7(core, core[-1:], core[:1],
                                    block_planes=block_planes),
                 jax.jit(_rolled_stencil)(core))


@pytest.mark.parametrize("shape", [(4, 3, 5), (4, 4, 4), (1, 8, 128)])
def test_faces_untiled_stencil_is_the_rolled_expression(shape):
    from repro.core.halo import _interior_fn, stencil_lowering

    assert stencil_lowering(shape, np.float32) == "jnp"
    u = rand(np.random.RandomState(4), (1, 1, 1) + shape, "float32")
    np.testing.assert_array_equal(
        np.asarray(jax.jit(_interior_fn)(u))[0, 0, 0],
        np.asarray(jax.jit(_rolled_stencil)(u[0, 0, 0])))


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------


@pytest.mark.parametrize("rows,d", [(1, 64), (37, 256), (128, 128), (5, 1024)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("offset", [0.0, 1.0])
def test_rmsnorm_sweep(rows, d, dtype, offset):
    rng = np.random.RandomState(3)
    x = rand(rng, (rows, d), dtype)
    w = rand(rng, (d,), dtype)
    got = ops.rmsnorm(x, w, weight_offset=offset, block_rows=32)
    want = ref.rmsnorm(x, w, weight_offset=offset)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == "bfloat16" else 2e-5,
                               atol=1e-2 if dtype == "bfloat16" else 1e-5)


def test_rmsnorm_leading_dims():
    rng = np.random.RandomState(4)
    x = rand(rng, (2, 3, 5, 64), "float32")
    w = rand(rng, (64,), "float32")
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, w)),
                               np.asarray(ref.rmsnorm(x, w)), rtol=2e-5)


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

ATTN_CASES = [
    dict(B=1, Hq=2, Hkv=1, Sq=64, Skv=64, D=32, causal=True),
    dict(B=2, Hq=4, Hkv=4, Sq=48, Skv=48, D=16, causal=False),
    dict(B=1, Hq=8, Hkv=2, Sq=32, Skv=96, D=64, causal=True, q_offset=64),
    dict(B=1, Hq=2, Hkv=2, Sq=64, Skv=64, D=32, causal=True, window=19),
    dict(B=1, Hq=2, Hkv=1, Sq=64, Skv=64, D=32, causal=True,
         logit_softcap=15.0),
    dict(B=2, Hq=4, Hkv=1, Sq=1, Skv=80, D=32, causal=True, q_offset=79),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_sweep(case):
    case = dict(case)
    rng = np.random.RandomState(5)
    B, Hq, Hkv, Sq, Skv, D = (case.pop(k) for k in
                              ("B", "Hq", "Hkv", "Sq", "Skv", "D"))
    q = rand(rng, (B, Hq, Sq, D), "float32")
    k = rand(rng, (B, Hkv, Skv, D), "float32")
    v = rand(rng, (B, Hkv, Skv, D), "float32")
    got = ops.flash_attention(q, k, v, block_q=32, block_k=32, **case)
    want = ref.attention(q, k, v, **case)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=3e-5)


@pytest.mark.parametrize("dtype", ["bfloat16"])
def test_flash_attention_bf16(dtype):
    rng = np.random.RandomState(6)
    q = rand(rng, (1, 2, 64, 32), dtype)
    k = rand(rng, (1, 2, 64, 32), dtype)
    v = rand(rng, (1, 2, 64, 32), dtype)
    got = ops.flash_attention(q, k, v, block_q=32, block_k=32)
    want = ref.attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=3e-2)


def test_flash_attention_unaligned_padding():
    """Sq/Skv not multiples of the block — wrapper pads and un-pads."""
    rng = np.random.RandomState(7)
    q = rand(rng, (1, 2, 50, 32), "float32")
    k = rand(rng, (1, 1, 70, 32), "float32")
    v = rand(rng, (1, 1, 70, 32), "float32")
    got = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=3e-5)


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------

SSD_CASES = [
    dict(B=1, S=32, H=2, P=8, G=1, N=8, chunk=8),
    dict(B=2, S=80, H=4, P=16, G=2, N=24, chunk=32),   # padding (80 % 32)
    dict(B=1, S=128, H=2, P=32, G=1, N=16, chunk=128),  # single chunk
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_sweep(case):
    rng = np.random.RandomState(8)
    B, S, H, P, G, N, chunk = (case[k] for k in
                               ("B", "S", "H", "P", "G", "N", "chunk"))
    x = rand(rng, (B, S, H, P), "float32")
    dt = jnp.abs(rand(rng, (B, S, H), "float32")) * 0.1
    A = -jnp.abs(rand(rng, (H,), "float32"))
    Bm = rand(rng, (B, S, G, N), "float32")
    C = rand(rng, (B, S, G, N), "float32")
    y, h = ops.ssd_scan(x, dt, A, Bm, C, chunk=chunk, return_state=True)
    yr, hr = ref.ssd_scan(x, dt, A, Bm, C, return_state=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=2e-4, atol=3e-5)


def test_ssd_with_initial_state():
    rng = np.random.RandomState(9)
    B, S, H, P, G, N = 1, 40, 2, 8, 1, 8
    x = rand(rng, (B, S, H, P), "float32")
    dt = jnp.abs(rand(rng, (B, S, H), "float32")) * 0.1
    A = -jnp.abs(rand(rng, (H,), "float32"))
    Bm = rand(rng, (B, S, G, N), "float32")
    C = rand(rng, (B, S, G, N), "float32")
    h0 = rand(rng, (B, H, P, N), "float32")
    y, h = ops.ssd_scan(x, dt, A, Bm, C, init_state=h0, chunk=8,
                        return_state=True)
    yr, hr = ref.ssd_scan(x, dt, A, Bm, C, init_state=h0, return_state=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-4,
                               atol=3e-5)


def test_ssd_step_consistent_with_scan():
    """Decode recurrence step-by-step == full scan (serving invariant)."""
    rng = np.random.RandomState(10)
    B, S, H, P, G, N = 1, 12, 2, 4, 1, 6
    x = rand(rng, (B, S, H, P), "float32")
    dt = jnp.abs(rand(rng, (B, S, H), "float32")) * 0.2
    A = -jnp.abs(rand(rng, (H,), "float32"))
    Bm = rand(rng, (B, S, G, N), "float32")
    C = rand(rng, (B, S, G, N), "float32")
    y_scan = ref.ssd_scan(x, dt, A, Bm, C)
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, state = ref.ssd_step(x[:, t], dt[:, t], A, Bm[:, t], C[:, t], state)
        ys.append(y)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_scan),
                               rtol=2e-4, atol=3e-5)
