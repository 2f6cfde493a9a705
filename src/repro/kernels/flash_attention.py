"""Blockwise (flash) attention forward — Pallas TPU kernel.

The prefill/serve hot-spot.  Online-softmax blockwise attention with:

* GQA head mapping (q-head → kv-head via BlockSpec index map, no
  repeat-materialization of K/V in HBM);
* causal masking with a global ``q_offset`` (chunked prefill / decode);
* optional sliding window (gemma3 local layers, windowed serving);
* optional logit soft-capping (grok-style);
* fp32 accumulation in VMEM scratch.

Tiling: grid = (batch·q_heads, Sq/block_q, Skv/block_k), kv innermost
(sequential accumulation; TPU grids execute serially so scratch carries
state across the kv dimension).  Q/K/V tiles are (block_q|k, head_dim)
in VMEM; MXU dims are multiples of 128 when block sizes are (the
wrapper defaults to 128/256 and pads the sequence).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_body(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, window: Optional[int],
                logit_softcap: Optional[float], q_offset: int,
                kv_valid: int, block_q: int, block_k: int, n_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q + q_offset
    k_start = ik * block_k

    # Block-level skip: fully-masked (causal / window / padding) kv tiles.
    run = k_start < kv_valid
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)      # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)      # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_softcap is not None:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_valid
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...][:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        p = jnp.exp(s - m_new)                          # [bq, bk]
        p = jnp.where(mask, p, 0.0)

        l_prev = l_ref[...][:, :1]
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[...][:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def flash_attention_call(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, "GQA requires Hq % Hkv == 0"
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale

    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    pad_q = (-Sq) % block_q
    pad_k = (-Skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (Sq + pad_q) // block_q
    n_k = (Skv + pad_k) // block_k

    body = functools.partial(
        _flash_body, scale=scale, causal=causal, window=window,
        logit_softcap=logit_softcap, q_offset=q_offset, kv_valid=Skv,
        block_q=block_q, block_k=block_k, n_k=n_k)

    out = pl.pallas_call(
        body,
        grid=(B * Hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda bh, iq, ik: (bh // Hq, bh % Hq, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bh, iq, ik: (bh // Hq, (bh % Hq) // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda bh, iq, ik: (bh // Hq, (bh % Hq) // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda bh, iq, ik: (bh // Hq, bh % Hq, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq] if pad_q else out
