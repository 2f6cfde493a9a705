"""Pallas TPU kernels for Faces boundary packing (paper §V-A steps 2/6).

The paper's Faces benchmark launches GPU kernels that "copy into
contiguous MPI buffers from faces, edges, and corners of spectral
elements" before sending, and kernels that add received messages back
after the wait.  These are the compute hot-spots of the communication
loop, so they get Pallas kernels:

* ``halo_pack_call``       — extract one static boundary slab;
* ``halo_unpack_add_call`` — add one received slab into the block, in
                             place (the block is aliased to the output);
* ``pack_segments_call``   — N *separate* source slabs into ONE
                             contiguous staging buffer at static
                             offsets: the same layout a
                             :class:`~repro.core.matching.CoalescedChannel`
                             fused transfer stages (members send from
                             distinct buffers).  The engines currently
                             lower that pack with ``jnp.concatenate``;
                             this kernel is the parity-tested drop-in,
                             not yet wired into ``_run_coalesced_batch``;
* ``unpack_segments_call`` — split the received staging buffer back into
                             the per-member slabs (inverse; same status).

TPU adaptation: a Faces block is far larger than VMEM (256³ float32 is
64 MiB), so the slab kernels never load the block whole.  Each one
pipelines, over chunks of the slab's leading axis, only the *window* of
the block that holds the slab: along the two minor axes the window is
the smallest (sublane, lane)-tile-aligned block containing the slab's
extent (or the whole axis where the axis is not tile-divisible), so a
z-face reads a 128-lane column band and an x-face one plane.  The
unpack-add kernel aliases the block to its output and writes back only
the visited windows; the rest of the block is never touched.  Both are
VPU copy/accumulate kernels (the MXU is not involved).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: VMEM bytes one pipelined block may take.  Pallas double-buffers every
#: operand, so the unpack-add kernel's three blocks stay under 12 MiB of
#: the 16 MiB scoped-VMEM default of a v5e.
_BLOCK_BYTES = 2 << 20

_LANES = 128


def _region_shape(region: Tuple[slice, ...]) -> Tuple[int, ...]:
    return tuple(s.stop - s.start for s in region)


def _sublanes(dtype) -> int:
    """Second-minor tile height of a VMEM array of ``dtype`` (32-bit
    words hold 1, 2 or 4 elements)."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def _tiled_bytes(shape, dtype) -> int:
    """VMEM bytes of a block of ``shape`` once its two minor axes are
    padded to whole tiles."""
    *lead, rows, cols = shape
    sub = _sublanes(dtype)
    padded = (-(-rows // sub) * sub) * (-(-cols // _LANES) * _LANES)
    return int(np.prod(lead, dtype=np.int64)) * padded * np.dtype(dtype).itemsize


def _axis_window(start: int, stop: int, n: int, tile: int) -> Tuple[int, int]:
    """``(block, index)`` of the smallest tile-aligned block along an
    axis of length ``n`` that holds ``[start, stop)``."""
    if n % tile:
        return n, 0  # a block equal to the whole axis is always legal
    for w in range(tile, n + 1, tile):
        if n % w == 0 and start // w == (stop - 1) // w:
            return w, start // w
    return n, 0


@dataclasses.dataclass(frozen=True)
class _SlabWindow:
    """How a slab kernel walks ``region`` of a 3-D block: ``grid`` steps
    of ``chunk`` leading-axis planes, each reading the block window
    ``block`` at ``index(i)``; ``rel`` locates the slab in the window."""

    chunk: int
    grid: int
    block: Tuple[int, int, int]
    minor_index: Tuple[int, int]
    lead_offset: int
    rel: Tuple[slice, ...]

    def index(self, i):
        return (self.lead_offset + i, *self.minor_index)


def _slab_window(shape, region, dtype) -> _SlabWindow:
    if len(shape) != 3 or len(region) != 3:
        raise ValueError(
            f"slab kernels take a 3-D block and region, got shape {shape}")
    for s, n in zip(region, shape):
        if not 0 <= s.start < s.stop <= n:
            raise ValueError(f"region {region} is outside block {shape}")
    (a0, b0), (a1, b1), (a2, b2) = ((s.start, s.stop) for s in region)
    w1, k1 = _axis_window(a1, b1, shape[1], _sublanes(dtype))
    w2, k2 = _axis_window(a2, b2, shape[2], _LANES)
    # chunks of c planes must tile [a0, b0) on block boundaries: c | a0
    # and c | (b0 - a0); take the largest whose windows fit the budget
    lead = math.gcd(a0, b0 - a0)
    slab = _region_shape(region)
    chunk = max(
        (c for c in range(1, lead + 1) if lead % c == 0
         and max(_tiled_bytes((c, w1, w2), dtype),
                 _tiled_bytes((c,) + slab[1:], dtype)) <= _BLOCK_BYTES),
        default=1)
    rel = (slice(0, chunk), slice(a1 - k1 * w1, b1 - k1 * w1),
           slice(a2 - k2 * w2, b2 - k2 * w2))
    return _SlabWindow(chunk=chunk, grid=(b0 - a0) // chunk,
                       block=(chunk, w1, w2), minor_index=(k1, k2),
                       lead_offset=a0 // chunk, rel=rel)


# --------------------------------------------------------------------------
# single-slab pack / unpack
# --------------------------------------------------------------------------


def halo_pack_call(u: jax.Array, region: Tuple[slice, ...], *,
                   interpret: bool = False) -> jax.Array:
    """``u[region]`` for a static region of a 3-D block."""
    win = _slab_window(u.shape, region, u.dtype)
    slab = _region_shape(region)

    def body(u_ref, out_ref):
        out_ref[...] = u_ref[win.rel]

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct(slab, u.dtype),
        grid=(win.grid,),
        in_specs=[pl.BlockSpec(win.block, win.index)],
        out_specs=pl.BlockSpec((win.chunk,) + slab[1:], lambda i: (i, 0, 0)),
        interpret=interpret,
    )(u)


def halo_unpack_add_call(u: jax.Array, msg: jax.Array,
                         region: Tuple[slice, ...], *,
                         interpret: bool = False) -> jax.Array:
    """``u.at[region].add(msg)``, updating ``u``'s buffer in place: only
    the windows holding the slab are read and written back."""
    win = _slab_window(u.shape, region, u.dtype)
    slab = _region_shape(region)
    if tuple(msg.shape) != slab:
        raise ValueError(f"message shape {msg.shape} != region shape {slab}")

    def body(u_ref, msg_ref, out_ref):
        out_ref[...] = u_ref[...]
        out_ref[win.rel] = u_ref[win.rel] + msg_ref[...].astype(u_ref.dtype)

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        grid=(win.grid,),
        in_specs=[
            pl.BlockSpec(win.block, win.index),
            pl.BlockSpec((win.chunk,) + slab[1:], lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(win.block, win.index),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(u, msg)


# --------------------------------------------------------------------------
# multi-source segment pack / unpack (channel-coalescing staging buffers)
# --------------------------------------------------------------------------


def _pack_segments_body(*refs):
    *in_refs, out_ref = refs
    off = 0
    for r in in_refs:  # static unroll over the group's members
        size = r.shape[0]
        out_ref[pl.ds(off, size)] = r[...]
        off += size


def pack_segments_call(arrays: Sequence[jax.Array], *,
                       interpret: bool = False) -> jax.Array:
    """Pack N source slabs into ONE contiguous 1-D staging buffer.

    The paper's contiguous MPI buffer: member slabs live in *separate*
    buffers (one per matched channel), and each lands
    at a static offset — the layout recorded in the batch's
    :class:`~repro.core.matching.CoalescePlan`.  All slabs must share a
    dtype (the plan groups by dtype).  Slabs are flattened before the
    kernel (Mosaic lowers no in-kernel reshape of a tiled slab to 1-D),
    so the kernel copies 1-D segments only.

    Status: the engines stage this layout with ``jnp.concatenate``
    (see ``engine_fused._run_coalesced_batch``); this kernel is the
    TPU drop-in for that pack, parity-tested but not yet wired in.
    """
    arrays = list(arrays)
    if not arrays:
        raise ValueError("pack_segments_call needs at least one slab")
    dtype = arrays[0].dtype
    if any(a.dtype != dtype for a in arrays):
        raise ValueError("coalesced segments must share a dtype")
    flat = [jnp.reshape(a, (-1,)) for a in arrays]
    total = sum(a.shape[0] for a in flat)
    return pl.pallas_call(
        _pack_segments_body,
        out_shape=jax.ShapeDtypeStruct((total,), dtype),
        in_specs=[pl.BlockSpec(a.shape, lambda: (0,)) for a in flat],
        out_specs=pl.BlockSpec((total,), lambda: (0,)),
        interpret=interpret,
    )(*flat)


def _unpack_segments_body(buf_ref, *out_refs):
    off = 0
    for r in out_refs:  # static unroll
        size = r.shape[0]
        r[...] = buf_ref[pl.ds(off, size)]
        off += size


def unpack_segments_call(buf: jax.Array, shapes: Sequence[Tuple[int, ...]], *,
                         interpret: bool = False) -> Tuple[jax.Array, ...]:
    """Split a received staging buffer back into per-member slabs
    (inverse of :func:`pack_segments_call`, static offsets; the kernel
    writes 1-D segments, reshaped to the slabs outside it)."""
    shapes = [tuple(s) for s in shapes]
    sizes = [int(np.prod(s)) for s in shapes]
    if sum(sizes) != int(np.prod(buf.shape)):
        raise ValueError(
            f"segment shapes cover {sum(sizes)} elements, buffer has "
            f"{int(np.prod(buf.shape))}")
    outs = pl.pallas_call(
        _unpack_segments_body,
        out_shape=tuple(jax.ShapeDtypeStruct((n,), buf.dtype) for n in sizes),
        in_specs=[pl.BlockSpec(buf.shape, lambda: (0,))],
        out_specs=tuple(pl.BlockSpec((n,), lambda: (0,)) for n in sizes),
        interpret=interpret,
    )(buf)
    outs = outs if isinstance(outs, (tuple, list)) else (outs,)
    return tuple(o.reshape(s) for o, s in zip(outs, shapes))
