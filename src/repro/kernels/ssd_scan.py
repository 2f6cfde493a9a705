"""Mamba2 SSD chunked-scan Pallas kernel (state-space duality form).

TPU adaptation of the SSD algorithm (Dao & Gu, arXiv:2405.21060): the
sequence is processed in chunks; **within** a chunk the recurrence is
re-expressed as matmuls (MXU work), and **across** chunks only the
(P×N) state is carried:

    a_t   = A_h · dt_t                       (log decay, ≤ 0)
    cum_t = Σ_{u≤t} a_u                      (within chunk)
    Y_intra = ((C Bᵀ) ∘ L ∘ dt) X            L[t,u] = e^{cum_t−cum_u}·[t≥u]
    Y_state = (C ∘ e^{cum}) h_prevᵀ
    h_next  = e^{cum_L} h_prev + Xᵀ (B ∘ (e^{cum_L−cum}·dt))

Tiling: grid = (batch, heads, S/chunk); the chunk dimension is the
innermost, *sequential* grid axis, and the running state lives in VMEM
scratch that persists across grid steps (TPU grids execute serially).
All matmuls are (chunk×N)·(N×chunk), (chunk×chunk)·(chunk×P),
(P×chunk)·(chunk×N) — MXU-aligned when chunk, N, P are multiples of 128
(defaults: chunk 128, N 128, P 64⁺pad by wrapper).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_body(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
              y_ref, hout_ref, h_scr, *, n_chunks: int, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)  # [P, N]

    x = x_ref[0, :, 0].astype(jnp.float32)    # [chunk, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # [chunk]
    A = a_ref[0].astype(jnp.float32)          # scalar
    Bm = b_ref[0, :, 0].astype(jnp.float32)   # [chunk, N]
    C = c_ref[0, :, 0].astype(jnp.float32)    # [chunk, N]
    h_prev = h_scr[...]                        # [P, N]

    a = A * dt                                 # [chunk] (≤ 0)
    cum = jnp.cumsum(a)                        # [chunk]
    # decay matrix L[t,u] = exp(cum_t - cum_u) for t ≥ u
    diff = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)

    G = jax.lax.dot_general(C, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [chunk, chunk]
    G = G * L * dt[None, :]
    y_intra = jax.lax.dot_general(G, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [chunk, P]

    c_decay = C * jnp.exp(cum)[:, None]        # [chunk, N]
    y_state = jax.lax.dot_general(c_decay, h_prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [chunk, P]

    y_ref[0, :, 0] = (y_intra + y_state).astype(y_ref.dtype)

    cum_last = cum[-1]
    w = jnp.exp(cum_last - cum) * dt           # [chunk]
    h_inc = jax.lax.dot_general(x, Bm * w[:, None], (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [P, N]
    h_scr[...] = jnp.exp(cum_last) * h_prev + h_inc

    @pl.when(ic == n_chunks - 1)
    def _finish():
        hout_ref[0, 0] = h_scr[...]


def ssd_scan_call(
    x: jax.Array,    # [B, S, H, P]
    dt: jax.Array,   # [B, S, H]
    A: jax.Array,    # [H]
    Bm: jax.Array,   # [B, S, G, N]
    C: jax.Array,    # [B, S, G, N]
    *,
    init_state: Optional[jax.Array] = None,  # [B, H, P, N]
    chunk: int = 128,
    return_state: bool = False,
    interpret: bool = False,
):
    B, S, H, P = x.shape
    _, _, G, N = Bm.shape
    assert H % G == 0
    rep = H // G
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        # dt = 0 on padding → decay 1, input contribution 0 (state-safe)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (S + pad) // chunk
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)

    body = functools.partial(_ssd_body, n_chunks=n_chunks, chunk=chunk)
    y, h_last = pl.pallas_call(
        body,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c, _rep=rep: (b, c, h // _rep, 0)),
            pl.BlockSpec((1, chunk, 1, N), lambda b, h, c, _rep=rep: (b, c, h // _rep, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S + pad, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, A, Bm, C, init_state)
    y = y[:, :S] if pad else y
    if return_state:
        return y, h_last
    return y
