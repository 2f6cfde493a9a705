"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated
against (tests sweep shapes/dtypes and assert_allclose kernel vs ref).
No Pallas, no tiling — straight-line jnp.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# halo pack / unpack (Faces boundary slabs)
# --------------------------------------------------------------------------


def halo_pack(u: jax.Array, region: Tuple[slice, ...]) -> jax.Array:
    """Extract a boundary slab (static region) from a local block."""
    return u[region]


def halo_unpack_add(u: jax.Array, msg: jax.Array, region: Tuple[slice, ...]) -> jax.Array:
    """Add a received slab into the block's boundary region."""
    return u.at[region].add(msg.astype(u.dtype))


def faces_stencil7(core: jax.Array, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """7-point periodic smoothing of a (px, py, pz) block: ``lo``/``hi``
    (1, py, pz) are the x-neighbours of the first and last plane, y and
    z wrap inside the block."""
    ext = jnp.concatenate([lo, core, hi], axis=0)
    return core + 0.125 * (
        ext[:-2] + ext[2:]
        + jnp.roll(core, 1, 1) + jnp.roll(core, -1, 1)
        + jnp.roll(core, 1, 2) + jnp.roll(core, -1, 2)
        - 6.0 * core
    )


# --------------------------------------------------------------------------
# RMSNorm
# --------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            weight_offset: float = 0.0) -> jax.Array:
    """y = x / rms(x) * (w + offset); stats in fp32 (gemma uses offset=1)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (w.astype(jnp.float32) + weight_offset)).astype(x.dtype)


# --------------------------------------------------------------------------
# Flash attention (forward)
# --------------------------------------------------------------------------


def attention(
    q: jax.Array,           # [B, Hq, Sq, D]
    k: jax.Array,           # [B, Hkv, Skv, D]
    v: jax.Array,           # [B, Hkv, Skv, D]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,       # sliding window (tokens of lookback)
    logit_softcap: Optional[float] = None,
    q_offset: int = 0,      # global position of q[0] (decode/prefill chunk)
) -> jax.Array:
    """Reference GQA attention.  Hq must be a multiple of Hkv."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
    if logit_softcap is not None:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (can happen with windows) → zeros not NaNs
    probs = jnp.where(jnp.any(mask, -1)[None, None, :, None], probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vr.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba2 SSD (selective state space, scalar-identity A per head)
# --------------------------------------------------------------------------


def ssd_scan(
    x: jax.Array,    # [B, S, H, P]   head channels
    dt: jax.Array,   # [B, S, H]      softplus-ed step sizes (>0)
    A: jax.Array,    # [H]            negative decay rates
    Bm: jax.Array,   # [B, S, G, N]   input projection (G groups)
    C: jax.Array,    # [B, S, G, N]   output projection
    *,
    init_state: Optional[jax.Array] = None,  # [B, H, P, N]
    return_state: bool = False,
):
    """Reference SSD: h_t = exp(A·dt_t)·h_{t-1} + dt_t·(x_t ⊗ B_t);
    y_t = (h_t · C_t) per head.  Heads map to B/C groups by h // (H/G).
    Runs an explicit scan in fp32."""
    Bsz, S, H, P = x.shape
    _, _, G, N = Bm.shape
    assert H % G == 0
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)  # [B, S, H, N]
    Ch = jnp.repeat(C, rep, axis=2)

    decay = jnp.exp(A[None, None, :] * dt)          # [B, S, H]
    inc = dt[..., None, None] * (x[..., :, :, None] * Bh[..., None, :])
    # inc: [B, S, H, P, N]

    def step(h, inputs):
        d, i = inputs
        h = d[..., None, None] * h + i
        return h, h

    h0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    d_t = jnp.moveaxis(decay, 1, 0).astype(jnp.float32)
    i_t = jnp.moveaxis(inc, 1, 0).astype(jnp.float32)
    h_last, hs = jax.lax.scan(step, h0, (d_t, i_t))
    hs = jnp.moveaxis(hs, 0, 1)  # [B, S, H, P, N]
    y = jnp.einsum("bshpn,bshn->bshp", hs, Ch.astype(jnp.float32))
    y = y.astype(x.dtype)
    if return_state:
        return y, h_last.astype(jnp.float32)
    return y


def ssd_step(
    x: jax.Array,    # [B, H, P]
    dt: jax.Array,   # [B, H]
    A: jax.Array,    # [H]
    Bm: jax.Array,   # [B, G, N]
    C: jax.Array,    # [B, G, N]
    state: jax.Array,  # [B, H, P, N]
):
    """Single decode step of the SSD recurrence → (y, new_state)."""
    H = x.shape[1]
    G = Bm.shape[1]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=1)
    Ch = jnp.repeat(C, rep, axis=1)
    decay = jnp.exp(A[None, :] * dt)  # [B, H]
    new = decay[..., None, None] * state.astype(jnp.float32) + (
        dt[..., None, None] * (x[..., :, None] * Bh[:, :, None, :])
    ).astype(jnp.float32)
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch.astype(jnp.float32)).astype(x.dtype)
    return y, new
