"""Plain float32 forward pass of a Qwen1.5 (``Qwen2ForCausalLM``) decoder.

Follows the published architecture: token embedding; per layer an
RMSNorm, attention with biased q/k/v projections, rotary embedding of
the rotate-half form at ``rope_theta``, causal softmax attention scaled
by ``1/sqrt(head_dim)`` and an output projection, a residual add, an
RMSNorm, the gated MLP ``down(silu(gate(x)) * up(x))``, a residual add;
a final RMSNorm and the vocabulary projection, tied to the embedding
where the config says so.  Everything is float32 under
``jax.default_matmul_precision("highest")``; no cache, no batching.

It reads the weights in the layout they are given to the program under
test (a nested dict, layers stacked on a leading axis, q/k/v as
``[d, heads, head_dim]``), with one departure from the published
parameters noted: an RMSNorm weight is stored as ``w - 1``, so the
reference applies ``1 + stored``.

With ``quant=True`` both operands of every matrix product are rounded
to float8 (e4m3, one scale per tensor) first: the control that a
lower-precision path must fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ROWS = 512  # vocabulary projection in blocks of this many positions


def _q8(x):
    """Round to float8 e4m3 with one scale per tensor, back to float32."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(spec, a, b, quant):
    if quant:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, theta):
    """x: [T, H, D]; rotate-half rotary embedding at positions 0..T-1."""
    T, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, *, eps, theta, quant):
    T = x.shape[0]
    h = _rms(x, p["ln_attn"]["scale"], eps)
    a = p["attn"]
    q = _mm("td,dhe->the", h, a["wq"], quant) + a["bq"]
    k = _mm("td,dhe->the", h, a["wk"], quant) + a["bk"]
    v = _mm("td,dhe->the", h, a["wv"], quant) + a["bv"]
    q, k = _rope(q, theta), _rope(k, theta)
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = _mm("the,she->hts", q, k, quant) / np.sqrt(q.shape[-1])
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = _mm("hts,she->the", jax.nn.softmax(s, axis=-1), v, quant)
    x = x + _mm("the,hed->td", o, a["wo"], quant)
    h = _rms(x, p["ln_mlp"]["scale"], eps)
    m = p["mlp"]
    g = jax.nn.silu(_mm("td,df->tf", h, m["wg"], quant))
    u = _mm("td,df->tf", h, m["wi"], quant)
    return x + _mm("tf,fd->td", g * u, m["wo"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _trunk(w, tokens, *, eps, theta, quant):
    x = jnp.take(w["embed"]["table"], tokens, axis=0)

    def body(x, p):
        return _layer(x, p, eps=eps, theta=theta, quant=quant), None

    x, _ = jax.lax.scan(body, x, w["decoder"]["segments"][0])
    return _rms(x, w["ln_final"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, table, targets, *, quant):
    """Best logit, the logit of ``targets`` and the argmax, per row."""
    logits = _mm("td,vd->tv", h, table, quant)
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best, at, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def scores(w, cfg: dict, tokens, targets, quant: bool = False):
    """Run the decoder over ``tokens`` [T]; per position return the best
    logit, the logit of ``targets[t]`` and the argmax, as NumPy arrays.
    The vocabulary projection runs in blocks of :data:`ROWS` positions.
    """
    if not cfg.get("tie_word_embeddings"):
        raise NotImplementedError("untied vocabulary projection")
    with jax.default_matmul_precision("highest"):
        h = _trunk(w, jnp.asarray(tokens, jnp.int32),
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]), quant=quant)
        targets = jnp.asarray(targets, jnp.int32)
        T = h.shape[0]
        pad = (-T) % ROWS
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
        parts = [_head(h[i:i + ROWS], w["embed"]["table"],
                       targets[i:i + ROWS], quant=quant)
                 for i in range(0, T + pad, ROWS)]
    best, at, arg = (np.concatenate([np.asarray(p[j]) for p in parts])[:T]
                     for j in range(3))
    return best, at, arg


def served_gap(w, cfg: dict, prompt, served, quant: bool = False) -> float:
    """Widest gap by which a served token's logit lies below the
    reference's best, over the served positions of one request.

    ``served`` holds every token served for ``prompt``, the first from
    prefill.  With ``quant=True`` the tokens judged are not the served
    ones but those that the float8 control puts first at the same
    positions (the control's reading).
    """
    P = len(prompt)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    targets = np.concatenate([seq[1:], served[-1:]]).astype(np.int32)
    if quant:
        _, _, targets = scores(w, cfg, seq, targets, quant=True)
    best, at, _ = scores(w, cfg, seq, targets)
    return float(np.max(best[P - 1:] - at[P - 1:]))
