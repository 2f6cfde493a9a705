"""Pallas TPU kernel for the Faces interior stencil (paper §V-A step 4).

The overlap kernel of every Faces iteration is a 7-point periodic
smoothing of the rank's whole block::

    out = core + 0.125 * (xm + xp + ym + yp + zm + zp - 6 * core)

where ``xm``/``xp`` are the x-neighbour planes (the block's own wrap
planes for a whole domain, or the ghost planes a linked x-part receives)
and the y/z neighbours wrap inside the block.  Written with
``jnp.roll``, XLA materialises three of the six shifted copies as
whole-block temporaries in HBM and then reads them all back.

This kernel reads the block once and writes it once.  The grid walks
blocks of ``bx`` x-planes in order.  Each step holds one block, the
first plane of the next block (a second ``BlockSpec`` on the same
array, one plane tall, its index taken modulo the plane count) and the
previous block's last plane, carried in VMEM scratch, so no plane is
fetched twice from HBM except the one lookahead plane per block.  The
ghost planes stand in for the missing neighbours at the two ends only.
The y and z neighbours are rotations of each plane inside VMEM
(``pltpu.roll`` along the sublane and lane axes).  The arithmetic is the
jnp expression's, in the same order, so the result matches it to the
ULPs of float32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .halo_pack import _BLOCK_BYTES, _LANES, _sublanes, _tiled_bytes


def stencil7_tiles(shape, dtype) -> bool:
    """True where a (px, py, pz) block tiles the kernel: ``py`` a whole
    number of sublane tiles, ``pz`` of lane tiles, and at least two
    x-planes."""
    if len(shape) != 3:
        return False
    px, py, pz = shape
    return px >= 2 and py % _sublanes(dtype) == 0 and pz % _LANES == 0


def _planes_per_block(shape, dtype) -> int:
    """The most x-planes per block that divide ``px`` and fit
    ``_BLOCK_BYTES``; at least one."""
    px, py, pz = shape
    plane = _tiled_bytes((1, py, pz), dtype)
    return max(b for b in range(1, px + 1)
               if px % b == 0 and b * plane <= max(plane, _BLOCK_BYTES))


def _smooth(c, xm, xp):
    """One plane of ``c + 0.125 * (xm + xp + ym + yp + zm + zp - 6 * c)``,
    summed left to right as written.  ``lax`` primitives, not ``jnp``
    operators: each of those is a jitted function traced anew inside the
    kernel, about 80 traces per kernel over the unrolled planes, which
    took 0.6 s per kernel trace on a v5e host."""
    py, pz = c.shape
    s = lax.add(xm, xp)
    for n in (pltpu.roll(c, 1, 0), pltpu.roll(c, py - 1, 0),
              pltpu.roll(c, 1, 1), pltpu.roll(c, pz - 1, 1)):
        s = lax.add(s, n)
    s = lax.sub(s, lax.mul(6.0, c))
    return lax.add(c, lax.mul(0.125, s))


def _stencil7_body(core_ref, next_ref, lo_ref, hi_ref, out_ref, below_ref):
    j = pl.program_id(0)
    bx = core_ref.shape[0]

    @pl.when(j == 0)
    def _():
        below_ref[...] = lo_ref[0]

    above = jnp.where(j == pl.num_programs(0) - 1, hi_ref[0], next_ref[0])
    for i in range(bx):  # static unroll over the block's planes
        xm = below_ref[...] if i == 0 else core_ref[i - 1]
        xp = above if i == bx - 1 else core_ref[i + 1]
        out_ref[i] = _smooth(core_ref[i], xm, xp)
    below_ref[...] = core_ref[bx - 1]


def faces_stencil7_call(core: jax.Array, lo: jax.Array, hi: jax.Array, *,
                        block_planes: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """7-point periodic smoothing of ``core`` (px, py, pz), with ``lo``
    and ``hi`` (1, py, pz) as the x-neighbours of its first and last
    plane.  Needs :func:`stencil7_tiles`.  ``block_planes`` (a divisor
    of ``px``) overrides the planes per grid step."""
    if not stencil7_tiles(core.shape, core.dtype):
        raise ValueError(f"block {core.shape} of {core.dtype} does not tile "
                         f"the stencil kernel")
    px, py, pz = core.shape
    for g in (lo, hi):
        if tuple(g.shape) != (1, py, pz) or g.dtype != core.dtype:
            raise ValueError(f"ghost plane {g.shape} {g.dtype} does not fit "
                             f"block {core.shape} {core.dtype}")
    bx = block_planes or _planes_per_block(core.shape, core.dtype)
    if px % bx:
        raise ValueError(f"{bx} planes per block do not divide {px}")
    plane = pl.BlockSpec((1, py, pz), lambda j: (0, 0, 0))
    return pl.pallas_call(
        _stencil7_body,
        out_shape=jax.ShapeDtypeStruct(core.shape, core.dtype),
        grid=(px // bx,),
        in_specs=[
            pl.BlockSpec((bx, py, pz), lambda j: (j, 0, 0)),
            pl.BlockSpec((1, py, pz), lambda j: (((j + 1) * bx) % px, 0, 0)),
            plane,
            plane,
        ],
        out_specs=pl.BlockSpec((bx, py, pz), lambda j: (j, 0, 0)),
        scratch_shapes=[pltpu.VMEM((py, pz), core.dtype)],
        # the carried plane needs the blocks in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="faces_stencil7",
        interpret=interpret,
    )(core, core, lo, hi)
