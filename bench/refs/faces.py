"""Plain Faces reference: the paper's 26-neighbour direct-stiffness update
written globally in ``jax.numpy``.

One iteration on a field ``u`` of shape ``(gx, gy, gz, px, py, pz)`` (a
``(px, py, pz)`` block per rank of a periodic ``gx x gy x gz`` grid):

1. every rank packs its 26 boundary slabs (faces, edges, corners) from
   its block as it was at the start of the iteration;
2. every block is smoothed by a 7-point stencil that wraps around within
   the block: ``u + 1/8 (sum of the 6 neighbours - 6 u)``;
3. the slab a rank packed for direction ``d`` is added into the opposite
   (``-d``) slab of the neighbour at ``+d`` (periodic over the grid);
4. the field is scaled by the damping factor.

The global RMS of the field after each iteration is the residual trace.
Every operation runs in ``dtype``: float32 is the reference, a lower
type serves as the control.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp

DIRECTIONS = tuple(d for d in itertools.product((-1, 0, 1), repeat=3)
                   if any(d))


def _side(s: int, n: int) -> slice:
    return slice(0, 1) if s < 0 else slice(n - 1, n) if s > 0 else slice(None)


def _slab(d, points):
    return tuple(_side(s, n) for s, n in zip(d, points))


def step(blocks, grid, damping: float):
    """One iteration over ``blocks``, the ranks' ``(px, py, pz)`` blocks
    keyed by their place in the periodic ``grid``.  Each block is its
    own array: held as one ``(gx, gy, gz, px, py, pz)`` array, the
    compiler may tile the small grid axes and pad the field many times
    over."""
    points = next(iter(blocks.values())).shape
    packed = {r: {d: b[_slab(d, points)] for d in DIRECTIONS}
              for r, b in blocks.items()}
    out = {}
    for r, u in blocks.items():
        o = u + 0.125 * (sum(jnp.roll(u, s, ax) for ax in range(3)
                             for s in (1, -1)) - 6 * u)
        for d in DIRECTIONS:
            src = tuple((r[a] - d[a]) % grid[a] for a in range(3))
            o = o.at[_slab(tuple(-x for x in d), points)].add(packed[src][d])
        if damping:
            o = o * jnp.asarray(damping, o.dtype)
        out[r] = o
    return out


@functools.partial(jax.jit, static_argnames=("n_iters", "damping", "dtype"))
def run(u0, *, n_iters: int, damping: float, dtype=jnp.float32):
    """``n_iters`` iterations from ``u0`` of shape ``(gx, gy, gz, px, py,
    pz)``: (final field, residual trace)."""
    grid = u0.shape[:3]
    ranks = list(itertools.product(*map(range, grid)))
    n = u0.size

    def body(blocks, _):
        blocks = step(dict(zip(ranks, blocks)), grid, damping)
        blocks = tuple(blocks[r] for r in ranks)
        total = sum(jnp.sum(jnp.square(b), dtype=dtype) for b in blocks)
        return blocks, jnp.sqrt(total / n)

    blocks, res = jax.lax.scan(
        body, tuple(u0[r].astype(dtype) for r in ranks), None, length=n_iters)
    return jnp.stack(blocks).reshape(u0.shape), res
