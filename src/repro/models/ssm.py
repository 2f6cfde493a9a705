"""Mamba2 (SSD) block — attention-free selective state-space layer.

Structure (arXiv:2405.21060): in_proj → [z | x | B | C | dt]; short
causal depthwise conv on (x,B,C); SSD scan (Pallas kernel or jnp
oracle); gated RMSNorm; out_proj.

Distribution note: the SSD inner dimension shards over
``model`` (heads), and for sequence-parallel long-context the
chunk-boundary state hand-off is a ppermute chain — the ST trigger/wait
pattern.  Decode carries (conv_state, ssm_state) instead of a KV cache:
O(1) memory in sequence length, which is why mamba2/hymba run
``long_500k``.
"""

from __future__ import annotations

import math
from functools import lru_cache
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel import act_shard
from .nn import param


@lru_cache(maxsize=None)
def _ssd_kernel_diff(chunk: int):
    """Pallas SSD forward with reference-oracle gradients.

    The Pallas kernel has no JVP rule (VMEM scratch), so the backward
    pass differentiates the pure-jnp oracle — on TPU this acts like a
    remat'd reference backward while the forward keeps the kernel."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    @jax.custom_vjp
    def f(xh, dt, A, Bg, Cg):
        return kops.ssd_scan(xh, dt, A, Bg, Cg, chunk=chunk, return_state=True)

    def fwd(xh, dt, A, Bg, Cg):
        return f(xh, dt, A, Bg, Cg), (xh, dt, A, Bg, Cg)

    def bwd(res, ct):
        _, vjp = jax.vjp(
            lambda *a: kref.ssd_scan(*a, return_state=True), *res)
        return vjp(ct)

    f.defvjp(fwd, bwd)
    return f


def ssm_dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    conv_dim = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_inner, n_heads, conv_dim


def init_ssm(key, cfg: ModelConfig):
    d = cfg.d_model
    d_inner, H, conv_dim = ssm_dims(cfg)
    G, N, P = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    d_in_proj = 2 * d_inner + 2 * G * N + H
    return {
        "in_proj": param(ks[0], (d, d_in_proj), ("embed", "act_mlp"), dt),
        "conv_w": param(ks[1], (cfg.ssm_conv, conv_dim), ("conv", "act_mlp"), dt,
                        scale=1.0 / math.sqrt(cfg.ssm_conv)),
        "conv_b": param(ks[1], (conv_dim,), ("act_mlp",), dt, init="zeros"),
        "A_log": param(ks[2], (H,), ("heads",), jnp.dtype("float32"), init="ones"),
        "D": param(ks[3], (H,), ("heads",), jnp.dtype("float32"), init="ones"),
        "dt_bias": param(ks[4], (H,), ("heads",), jnp.dtype("float32"), init="zeros"),
        "norm": param(ks[5], (d_inner,), ("act_mlp",), dt, init="zeros"),
        "out_proj": param(ks[5], (d_inner, d), ("act_mlp", "embed"), dt,
                          scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }


def _split_proj(zxbcdt, cfg: ModelConfig):
    d_inner, H, _ = ssm_dims(cfg)
    G, N = cfg.ssm_groups, cfg.ssm_state
    z = zxbcdt[..., :d_inner]
    x = zxbcdt[..., d_inner:2 * d_inner]
    Bm = zxbcdt[..., 2 * d_inner:2 * d_inner + G * N]
    C = zxbcdt[..., 2 * d_inner + G * N:2 * d_inner + 2 * G * N]
    dt_raw = zxbcdt[..., 2 * d_inner + 2 * G * N:]
    return z, x, Bm, C, dt_raw


def _causal_conv(xbc, w, b, *, state: Optional[jax.Array] = None):
    """Depthwise causal conv.  xbc: [B,S,C]; w: [K,C].  With `state`
    ([B,K-1,C], decode), prepends it and returns (y, new_state)."""
    K = w.shape[0]
    if state is not None:
        full = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
        new_state = full[:, -(K - 1):] if K > 1 else state
    else:
        full = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        new_state = None
    # gather K shifted views (K is tiny: 4)
    S = xbc.shape[1]
    y = jnp.zeros_like(xbc, dtype=jnp.float32)
    for i in range(K):
        y = y + full[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    y = jax.nn.silu(y + b.astype(jnp.float32)).astype(xbc.dtype)
    return y, new_state


def _gated_norm(x, z, scale, eps):
    xf = (x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)).astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) *
            (scale.astype(jnp.float32) + 1.0)).astype(x.dtype)


def apply_ssm(p, xin, cfg: ModelConfig, *,
              cache: Optional[Dict] = None) -> Tuple[jax.Array, Optional[Dict]]:
    """xin: [B,S,D] → (y [B,S,D], new_cache | None).

    cache = {"conv": [B,K-1,conv_dim], "state": [B,H,P,N]} for decode.
    """
    B, S, D = xin.shape
    d_inner, H, conv_dim = ssm_dims(cfg)
    G, N, P = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_head_dim
    dt_ = xin.dtype

    zxbcdt = act_shard(jnp.einsum("bsd,de->bse", xin, p["in_proj"].astype(dt_)),
                       "batch", "seq", "act_mlp")
    z, x, Bm, C, dt_raw = _split_proj(zxbcdt, cfg)

    xbc = jnp.concatenate([x, Bm, C], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], state=conv_state)
    x = xbc[..., :d_inner]
    Bm = xbc[..., d_inner:d_inner + G * N]
    C = xbc[..., d_inner + G * N:]

    dt_v = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # [H] negative
    xh = act_shard(x.reshape(B, S, H, P), "batch", "seq", "act_heads", None)
    Bg = Bm.reshape(B, S, G, N)
    Cg = C.reshape(B, S, G, N)

    init_state = cache["state"] if cache is not None else None
    if cfg.use_ssd_kernel and cache is None:
        y, last = _ssd_kernel_diff(cfg.ssm_chunk)(xh, dt_v, A, Bg, Cg)
    else:
        from repro.kernels import ref as kref
        if cache is not None and S == 1:
            yh, last = kref.ssd_step(xh[:, 0], dt_v[:, 0], A, Bg[:, 0], Cg[:, 0],
                                     init_state)
            y = yh[:, None]
        else:
            y, last = kref.ssd_scan(xh, dt_v, A, Bg, Cg,
                                    init_state=init_state, return_state=True)

    y = y + xh * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, d_inner)
    y = _gated_norm(y, z, p["norm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(dt_))

    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv, "state": last}
    return out, new_cache
