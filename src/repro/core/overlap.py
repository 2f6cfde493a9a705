"""Decomposed, overlap-friendly collectives (beyond-paper §Perf lever).

The paper's §V-F result (hand-tuned shader triggers beat the stock
stream-memory ops by 8%) says: once control is on the device, *how* the
trigger/communication schedule is expressed decides the win.  The TPU
analogue: how a collective is *lowered* decides whether XLA can overlap
it with compute.  This module provides ppermute-decomposed collectives
whose per-step structure interleaves with per-chunk compute — the
"collective matmul" family (Wang et al.; used by MaxText et al.) —
expressed with the same trigger/tie primitives as the ST engines.

All functions are written for use **inside shard_map** over the given
axis name.

Provided:
* ``all_gather_ring``        — N-1 ppermute steps, uni/bidirectional;
* ``reduce_scatter_ring``    — ring reduce-scatter;
* ``all_gather_matmul``      — A[local] @ W, A gathered along the ring,
                               matmul chunks overlap the permutes;
* ``matmul_reduce_scatter``  — Y = X @ W with Y reduce-scattered,
                               chunk matmuls overlap the ring;
* ``all_to_all_ppermute``    — a2a as explicit ppermute rounds (MoE
                               dispatch building block).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import counters


def _shift_perm(n: int, delta: int):
    return [(i, (i + delta) % n) for i in range(n)]


# --------------------------------------------------------------------------
# ring collectives
# --------------------------------------------------------------------------


def all_gather_ring(x: jax.Array, axis: str, *, bidirectional: bool = True,
                    tile_axis: int = 0) -> jax.Array:
    """All-gather `x` along `axis` via ring ppermutes (tiled layout).

    Bidirectional halves the number of serial steps (ceil((n-1)/2)) by
    sending both ways — the ICI-friendly schedule on a torus.
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    chunks = [None] * n
    chunks[0] = x

    if not bidirectional:
        cur = x
        for step in range(1, n):
            cur = jax.lax.ppermute(cur, axis, _shift_perm(n, 1))
            chunks[step] = cur
    else:
        fwd = x
        bwd = x
        steps_fwd = (n - 1 + 1) // 2
        steps_bwd = (n - 1) // 2
        for s in range(1, steps_fwd + 1):
            fwd = jax.lax.ppermute(fwd, axis, _shift_perm(n, 1))
            chunks[s] = fwd
        for s in range(1, steps_bwd + 1):
            bwd = jax.lax.ppermute(bwd, axis, _shift_perm(n, -1))
            chunks[n - s] = bwd

    # chunk i currently holds data of rank (idx - i); roll into global order
    stacked = jnp.stack(chunks, axis=0)  # [n, ...]
    order = (idx - jnp.arange(n)) % n
    gathered = jnp.zeros_like(stacked).at[order].set(stacked)
    parts = [gathered[i] for i in range(n)]
    return jnp.concatenate(parts, axis=tile_axis)


def reduce_scatter_ring(x: jax.Array, axis: str, *, tile_axis: int = 0) -> jax.Array:
    """Ring reduce-scatter of `x` (full-size input, 1/n-size output)."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    size = x.shape[tile_axis]
    assert size % n == 0, "tile axis must divide by axis size"
    chunk = size // n

    xr = x.reshape(x.shape[:tile_axis] + (n, chunk) + x.shape[tile_axis + 1:])

    def own(i):
        return jnp.take(xr, i % n, axis=tile_axis)

    # classic ring: chunk k starts at rank k+1 and travels n-1 hops
    # (k+1 → … → k), accumulating each host's chunk-k on arrival; at
    # step s rank r therefore holds chunk (r-1-s), starting from (r-1).
    acc = own(idx - 1)
    for step in range(1, n):
        acc = jax.lax.ppermute(acc, axis, _shift_perm(n, 1))
        acc = acc + own(idx - 1 - step)
    return acc


# --------------------------------------------------------------------------
# overlapped compute-communication (collective matmul)
# --------------------------------------------------------------------------


def all_gather_matmul(x: jax.Array, w: jax.Array, axis: str,
                      *, transpose_w: bool = False) -> jax.Array:
    """Compute ``all_gather(x, axis) @ w`` with per-chunk overlap.

    ``x``: [m_local, k]; the gather is along rows (m).  ``w``: [k, n]
    (already local / replicated as the caller arranged).  Instead of
    gather-then-matmul (serializing all communication before any
    compute), each ring step's chunk multiplies while the next permute
    is in flight — on TPU, XLA schedules the ppermute DMA async.

    Returns [m_local * n_axis, n].
    """
    n_dev = jax.lax.axis_size(axis)
    if transpose_w:
        w = w.T
    if n_dev == 1:
        return x @ w
    idx = jax.lax.axis_index(axis)
    m_local = x.shape[0]
    out = jnp.zeros((m_local * n_dev, w.shape[1]), dtype=jnp.result_type(x, w))

    cur = x
    for step in range(n_dev):
        # chunk owned by rank (idx - step); place at its global offset
        part = cur @ w
        src = (idx - step) % n_dev
        out = jax.lax.dynamic_update_slice_in_dim(out, part.astype(out.dtype),
                                                  src * m_local, axis=0)
        if step != n_dev - 1:
            cur = jax.lax.ppermute(cur, axis, _shift_perm(n_dev, 1))
    return out


def matmul_reduce_scatter(x: jax.Array, w: jax.Array, axis: str) -> jax.Array:
    """Compute ``reduce_scatter(x @ w, axis)`` with per-chunk overlap.

    ``x``: [m, k_local]; ``w``: [k_local, n].  The logical product
    ``x @ w`` needs a sum over the axis (k is sharded); the result rows
    are scattered so each rank keeps m/n_dev rows.  The ring interleaves
    chunk matmuls with the accumulating permute.

    Returns [m // n_axis, n].
    """
    n_dev = jax.lax.axis_size(axis)
    y_local = x @ w  # [m, n] partial sum
    if n_dev == 1:
        return y_local
    idx = jax.lax.axis_index(axis)
    m = y_local.shape[0]
    assert m % n_dev == 0
    chunk = m // n_dev

    yr = y_local.reshape((n_dev, chunk) + y_local.shape[1:])

    def piece(i):
        return jnp.take(yr, i % n_dev, axis=0)

    # same ring schedule as reduce_scatter_ring: chunk (r-1-s) at step s
    acc = piece(idx - 1)
    for step in range(1, n_dev):
        acc = jax.lax.ppermute(acc, axis, _shift_perm(n_dev, 1))
        acc = acc + piece(idx - 1 - step)
    return acc


def all_to_all_ppermute(x: jax.Array, axis: str, *, split_axis: int = 0) -> jax.Array:
    """All-to-all as explicit ppermute rounds (MoE dispatch).

    ``x``'s `split_axis` is divided into n_dev blocks; block j goes to
    rank j.  Equivalent to ``jax.lax.all_to_all(tiled=True)`` but
    expressed as n-1 permutes the ST way (each round is one deferred
    descriptor batch).
    """
    n = jax.lax.axis_size(axis)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis)
    size = x.shape[split_axis]
    assert size % n == 0
    blk = size // n
    xr = x.reshape(x.shape[:split_axis] + (n, blk) + x.shape[split_axis + 1:])
    move = jnp.moveaxis(xr, split_axis, 0)  # [n, ..., blk, ...]

    out = jnp.zeros_like(move)
    # my own block stays
    out = out.at[idx].set(jnp.take(move, idx, axis=0))
    for delta in range(1, n):
        # send the block destined for rank (idx+delta)
        send = jnp.take(move, (idx + delta) % n, axis=0)
        recv = jax.lax.ppermute(send, axis, _shift_perm(n, delta))
        out = out.at[(idx - delta) % n].set(recv)
    back = jnp.moveaxis(out, 0, split_axis)
    return back.reshape(x.shape)


# --------------------------------------------------------------------------
# ST-queue integration helpers
# --------------------------------------------------------------------------


def triggered(fn, token):
    """Wrap a decomposed collective so its operand ties to an ST trigger
    token — lets model code schedule these under an STQueue batch."""
    @functools.wraps(fn)
    def wrapped(x, *args, **kwargs):
        _, (x,) = counters.tie(token, x)
        return fn(x, *args, **kwargs)
    return wrapped
