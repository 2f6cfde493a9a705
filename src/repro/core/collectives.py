"""Collective-matmul unification — ring collectives as ST programs.

`core/overlap.py` expresses the decomposed ("collective matmul")
family as plain shard_map functions: ring steps are inline
``lax.ppermute`` calls, invisible to the ST machinery.  This module
re-expresses the same decompositions as **first-class ST descriptors**:
each ring step is an ordinary trigger→wait channel
(``enqueue_send``/``enqueue_recv`` + ``enqueue_start``/``enqueue_wait``)
and each per-chunk matmul/copy is an ``enqueue_compute`` kernel — so
the collectives inherit, with zero extra code:

* trace-time matching + channel coalescing (:mod:`.matching`);
* STLint static verification (:mod:`.verify`, incl. the ring-specific
  rules ST013/ST014 added with this module);
* analytic pricing (:func:`repro.launch.costing.schedule_cost`) and
  knob tuning (:func:`repro.launch.tune.tune`);
* composition into multi-queue schedules (:func:`.schedule.compose`) —
  matmul chunks land in other queues' trigger→wait windows;
* persistent 1-dispatch execution (:mod:`.engine_persistent`).

Bit-identity contract: every builder reproduces the *exact* op
sequence of its `overlap.py` reference (same rotation direction, same
deposit offsets, same accumulate operand order), so results are
bitwise equal to the decomposed shard_map path — and to ``jax.lax``
for the pure-copy collectives (all-gather, all-to-all).  The in-place
ring rotation (send and recv on the SAME buffer, replace mode) is the
descriptor-level spelling of ``cur = ppermute(cur, +1)``: the fused
engine reads the pre-trigger value for the send and the full-ring
replace deposit overwrites every rank, which is exactly a permute.

Layout conventions (mirroring the `overlap_bench.py` reference specs):

``enqueue_all_gather``      buf global [n*m, ...] pspec (axis,);
                            out replicated () — final value is
                            rank-invariant (or per-chunk ``compute``
                            output rows, caller-chosen pspec).
``enqueue_reduce_scatter``  buf = per-rank full partials, global
                            [n*(n*c), ...] pspec (axis,); out global
                            [n*c, ...] pspec (axis,).
``enqueue_all_to_all``      buf/out global [n*(n*b), ...] pspec
                            (axis,): local block j goes to rank j.

The high-level builders (:func:`build_all_gather_matmul`,
:func:`build_matmul_reduce_scatter`, :func:`build_all_to_all`,
:func:`build_tp_block`) return ready STPrograms for the three
collective-matmul patterns plus the headline "transformer block as ST
schedule" (Megatron MLP with sequence parallelism: all-gather-matmul →
relu → matmul-reduce-scatter), each with a pure-jax ``reference``
companion for bit-identity checks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .descriptors import OffsetPeer
from .queue import QueueError, STProgram, STQueue


def _update_rows(buf, piece, row0):
    """Deposit ``piece`` at row offset ``row0`` (traced index OK)."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, piece.astype(buf.dtype), row0, axis=0)


class CollectiveQueue(STQueue):
    """STQueue + ring-collective enqueue verbs.

    Every verb below is sugar: it appends ordinary kernel / send /
    recv / start / wait descriptors to the queue, one trigger→wait
    gate per ring step.  Nothing engine- or verifier-visible is new —
    which is the point: the built program is matched, coalesced,
    linted, priced, tuned, composed, and persisted exactly like any
    hand-written ST program.
    """

    def _axis_n(self, axis: str) -> int:
        shape = dict(self.mesh.shape)
        if axis not in shape:
            raise QueueError(f"mesh has no axis {axis!r}")
        return shape[axis]

    def _ring_pair(self, buf: str, axis: str, delta: int, tag: int) -> None:
        """One in-place ring rotation channel: ``buf = ppermute(buf, delta)``
        once the surrounding start fires (send reads the pre-trigger
        value; the full-ring replace deposit overwrites every rank)."""
        self.enqueue_send(buf, OffsetPeer(axis, delta, periodic=True), tag)
        self.enqueue_recv(buf, OffsetPeer(axis, -delta, periodic=True), tag,
                          mode="replace")

    def _stage(self, base: str, shape: Sequence[int], dtype, pspec) -> str:
        """Declare an internal staging buffer with a non-colliding name."""
        name, i = base, 0
        while name in self._buffers:
            i += 1
            name = f"{base}_{i}"
        return self.buffer(name, shape, dtype, pspec)

    # -- the collective verbs ------------------------------------------------

    def enqueue_all_gather(self, buf: str, out: str, axis: str, *,
                           compute: Optional[Callable] = None,
                           reads: Sequence[str] = (),
                           bidirectional: bool = False,
                           tag_base: int = 0) -> None:
        """Ring all-gather of ``buf`` (sharded over ``axis``, dim 0) into
        ``out``, one trigger→wait gate per ring step.

        With ``compute`` set, each arriving chunk is transformed before
        its deposit — ``compute(chunk, *extra)`` where ``extra`` are the
        local values of ``reads`` — which makes this the
        ``all_gather_matmul`` pattern: the per-chunk matmul is enqueued
        INTO the ring's trigger→wait window, so under composition other
        queues' transfers overlap it.  ``bidirectional=True`` runs two
        counter-rotating rings (ceil((n-1)/2) gates instead of n-1),
        the torus-friendly schedule of ``overlap.all_gather_ring``.
        """
        n = self._axis_n(axis)
        spec = self._buffers[buf]
        if n > 1 and (not spec.pspec or spec.pspec[0] != axis):
            raise QueueError(
                f"all_gather buffer {buf!r} must shard dim 0 over {axis!r}, "
                f"got pspec {spec.pspec}")
        out_spec = self._buffers[out]
        # per-chunk deposit rows: the LOCAL out rows split n ways (out
        # may be replicated — pure gather — or axis-sharded, as when a
        # compute hook leaves a per-rank column block)
        sharded_out = bool(out_spec.pspec) and out_spec.pspec[0] == axis
        local_rows = out_spec.shape[0] // n if sharded_out else out_spec.shape[0]
        if local_rows % n:
            raise QueueError(
                f"all_gather out {out!r}: local dim 0 ({local_rows}) must "
                f"divide by axis size {n}")
        m_out = local_rows // n
        extra = tuple(reads)

        def deposit(step: int, delta: int, src_buf: str) -> None:
            def k(cur, o, *xs):
                idx = jax.lax.axis_index(axis)
                piece = compute(cur, *xs) if compute is not None else cur
                src = (idx - delta * step) % n
                return _update_rows(o, piece, src * m_out)
            self.enqueue_compute(k, reads=(src_buf, out) + extra,
                                 writes=(out,),
                                 name=f"ag_chunk{step:+d}" if delta > 0
                                 else f"ag_chunk{-step:+d}")

        deposit(0, 1, buf)  # own chunk: no communication needed
        if n == 1:
            return
        if not bidirectional:
            for step in range(1, n):
                self._ring_pair(buf, axis, +1, tag_base + step)
                self.enqueue_start()
                self.enqueue_wait()
                deposit(step, +1, buf)
            return
        # two counter-rotating rings sharing each start gate
        bwd = self._stage(f"{buf}@bwd", spec.shape, spec.dtype, spec.pspec)
        self.enqueue_compute(lambda v: v, reads=(buf,), writes=(bwd,),
                             name="ag_seed_bwd")
        steps_fwd = (n - 1 + 1) // 2
        steps_bwd = (n - 1) // 2
        for step in range(1, steps_fwd + 1):
            self._ring_pair(buf, axis, +1, tag_base + 2 * step)
            if step <= steps_bwd:
                self._ring_pair(bwd, axis, -1, tag_base + 2 * step + 1)
            self.enqueue_start()
            self.enqueue_wait()
            deposit(step, +1, buf)
            if step <= steps_bwd:
                deposit(step, -1, bwd)

    def enqueue_reduce_scatter(self, buf: str, out: str, axis: str, *,
                               tag_base: int = 0) -> None:
        """Ring reduce-scatter: ``buf`` holds per-rank full partial sums
        (local rows = n * chunk); ``out`` (local rows = chunk) receives
        the summed chunk owned by this rank.

        Same schedule as ``overlap.reduce_scatter_ring``: the
        accumulator seeds with own piece (idx-1), then n-1 gates each
        rotate it one hop (+1) and add the next local piece — the
        accumulate kernel sits inside the ring's trigger→wait window.
        """
        n = self._axis_n(axis)
        spec = self._buffers[buf]
        out_spec = self._buffers[out]
        if n > 1 and (not spec.pspec or spec.pspec[0] != axis):
            raise QueueError(
                f"reduce_scatter buffer {buf!r} must shard dim 0 over "
                f"{axis!r}, got pspec {spec.pspec}")
        m_local = spec.shape[0] // n  # local partial rows
        if m_local % n:
            raise QueueError(
                f"reduce_scatter {buf!r}: local rows ({m_local}) must "
                f"divide by axis size {n}")
        chunk = m_local // n
        if n > 1 and out_spec.shape[0] // n != chunk:
            raise QueueError(
                f"reduce_scatter out {out!r}: expected local rows {chunk}, "
                f"got {out_spec.shape[0] // n}")

        def piece(y, i):
            yr = y.reshape((n, chunk) + y.shape[1:])
            return jnp.take(yr, i % n, axis=0)

        def seed(y):
            idx = jax.lax.axis_index(axis)
            return piece(y, idx - 1)

        self.enqueue_compute(seed, reads=(buf,), writes=(out,),
                             name="rs_seed")
        for step in range(1, n):
            self._ring_pair(out, axis, +1, tag_base + step)
            self.enqueue_start()
            self.enqueue_wait()

            def acc(a, y, _s=step):
                idx = jax.lax.axis_index(axis)
                return a + piece(y, idx - 1 - _s)
            self.enqueue_compute(acc, reads=(out, buf), writes=(out,),
                                 name=f"rs_acc{step}")

    def enqueue_all_to_all(self, buf: str, out: str, axis: str, *,
                           tag_base: int = 0) -> None:
        """All-to-all: local block j of ``buf`` goes to rank j (tiled,
        ``split_axis=0``), as ONE start gate carrying n-1 staged
        channels — the descriptor-level spelling of
        ``overlap.all_to_all_ppermute``'s n-1 permute rounds, batched so
        coalescing/interleaving see the whole exchange at once.
        """
        n = self._axis_n(axis)
        spec = self._buffers[buf]
        if n > 1 and (not spec.pspec or spec.pspec[0] != axis):
            raise QueueError(
                f"all_to_all buffer {buf!r} must shard dim 0 over {axis!r}, "
                f"got pspec {spec.pspec}")
        rows_local = spec.shape[0] // n
        if rows_local % n:
            raise QueueError(
                f"all_to_all {buf!r}: local rows ({rows_local}) must divide "
                f"by axis size {n}")
        blk = rows_local // n

        def block(x, i):
            mv = x.reshape((n, blk) + x.shape[1:])
            return jnp.take(mv, i % n, axis=0)

        def own(x, o):
            idx = jax.lax.axis_index(axis)
            return _update_rows(o, block(x, idx), idx * blk)

        self.enqueue_compute(own, reads=(buf, out), writes=(out,),
                             name="a2a_own")
        if n == 1:
            return
        stages = []
        for delta in range(1, n):
            st = self._stage(f"{buf}@a2a{delta}",
                             (n * blk,) + tuple(spec.shape[1:]),
                             spec.dtype, spec.pspec)
            stages.append(st)

            def pack(x, _d=delta):
                idx = jax.lax.axis_index(axis)
                return block(x, idx + _d)
            self.enqueue_compute(pack, reads=(buf,), writes=(st,),
                                 name=f"a2a_pack{delta}")
        for delta, st in enumerate(stages, start=1):
            self._ring_pair(st, axis, delta, tag_base + delta)
        self.enqueue_start()
        self.enqueue_wait()
        for delta, st in enumerate(stages, start=1):
            def drop(s, o, _d=delta):
                idx = jax.lax.axis_index(axis)
                return _update_rows(o, s, ((idx - _d) % n) * blk)
            self.enqueue_compute(drop, reads=(st, out), writes=(out,),
                                 name=f"a2a_drop{delta}")


# --------------------------------------------------------------------------
# built-program builders (collective-matmul family + TP block)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CollectiveMatmul:
    """A built collective-matmul ST program + its pure-jax oracles.

    ``program`` is engine-ready; ``inputs`` names the buffers the
    caller seeds; ``output`` names the result buffer.

    ``reference`` is the BIT-IDENTITY oracle: the decomposed
    ``overlap.py`` lowering inside shard_map, whose op sequence the ST
    program reproduces exactly — results must match with
    ``assert_array_equal``.  ``reference_stock`` is the stock
    ``jax.lax`` collective lowering (the perf baseline the bench races
    against); it is ALSO bitwise for the pure-copy collectives
    (all-gather, all-to-all) but only allclose where a ring sum
    reorders floating-point accumulation (reduce-scatter — the same
    tolerance `overlap.py`'s own tests use against ``psum_scatter``).
    """

    program: STProgram
    inputs: Tuple[str, ...]
    output: str
    reference: Callable[..., Any]
    reference_stock: Optional[Callable[..., Any]] = None


def _smap_ref(mesh, fn, in_specs, out_specs):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


def build_all_gather_matmul(mesh, axis: str, m: int, k: int, n_out: int,
                            dtype=np.float32, *, bidirectional: bool = False,
                            compute: Optional[Callable] = None,
                            verify: str = "warn",
                            name: str = "st_ag_matmul") -> CollectiveMatmul:
    """``all_gather(x) @ w`` as an ST program (x row-sharded, w
    replicated, out replicated — `overlap_bench`'s reference specs).

    ``m`` is the GLOBAL gathered row count; per-chunk matmuls are
    enqueued into the ring's trigger→wait windows.  ``compute``
    overrides the per-chunk op (default ``chunk @ w``) — e.g. add a
    fused nonlinearity.
    """
    n = dict(mesh.shape)[axis]
    if m % n:
        raise QueueError(f"m ({m}) must divide by axis size {n}")
    out_dtype = jnp.result_type(dtype, dtype)
    q = CollectiveQueue(mesh, name)
    q.buffer("x", (m, k), dtype, pspec=(axis,))
    q.buffer("w", (k, n_out), dtype, pspec=())
    q.buffer("out", (m, n_out), out_dtype, pspec=())
    q.enqueue_all_gather(
        "x", "out", axis,
        compute=compute or (lambda chunk, w: chunk @ w),
        reads=("w",), bidirectional=bidirectional)
    prog = q.build(verify=verify)

    from . import overlap

    def ref_body(x, w):
        # custom per-chunk hooks must be row-wise (fn(concat(chunks)) ==
        # concat(fn(chunk))) for the gathered-then-applied oracle to
        # stay bitwise — true of matmul + elementwise ops
        fn = compute or (lambda chunk, ww: chunk @ ww)
        if compute is None:
            return overlap.all_gather_matmul(x, w, axis)
        gathered = overlap.all_gather_ring(x, axis, bidirectional=False)
        return fn(gathered, w)

    from jax.sharding import PartitionSpec as P
    reference = _smap_ref(mesh, ref_body, (P(axis), P()), P())
    stock = _smap_ref(
        mesh,
        lambda x, w: jax.lax.all_gather(x, axis, axis=0, tiled=True) @ w
        if compute is None else ref_body(x, w),
        (P(axis), P()), P())
    return CollectiveMatmul(prog, ("x", "w"), "out", reference, stock)


def build_matmul_reduce_scatter(mesh, axis: str, m: int, k: int, n_out: int,
                                dtype=np.float32, *, verify: str = "warn",
                                name: str = "st_matmul_rs") -> CollectiveMatmul:
    """``reduce_scatter(x @ w)`` as an ST program (x column-sharded over
    k, w row-sharded over k, out row-sharded — `overlap_bench` specs).

    The partial matmul is one compute kernel; the accumulate kernels
    ride the ring gates (`overlap.matmul_reduce_scatter` schedule).
    """
    n = dict(mesh.shape)[axis]
    if m % n:
        raise QueueError(f"m ({m}) must divide by axis size {n}")
    q = CollectiveQueue(mesh, name)
    q.buffer("x", (m, k), dtype, pspec=(None, axis))
    q.buffer("w", (k, n_out), dtype, pspec=(axis,))
    # per-rank full partials: local rows = m, so global rows = n*m
    q.buffer("y", (n * m, n_out), dtype, pspec=(axis,))
    q.buffer("out", (m, n_out), dtype, pspec=(axis,))
    q.enqueue_compute(lambda x, w: x @ w, reads=("x", "w"), writes=("y",),
                      name="partial_matmul")
    q.enqueue_reduce_scatter("y", "out", axis)
    prog = q.build(verify=verify)

    from . import overlap
    from jax.sharding import PartitionSpec as P
    reference = _smap_ref(
        mesh, lambda x, w: overlap.matmul_reduce_scatter(x, w, axis),
        (P(None, axis), P(axis)), P(axis))
    stock = _smap_ref(
        mesh,
        lambda x, w: jax.lax.psum_scatter(x @ w, axis, scatter_dimension=0,
                                          tiled=True),
        (P(None, axis), P(axis)), P(axis))
    return CollectiveMatmul(prog, ("x", "w"), "out", reference, stock)


def build_all_to_all(mesh, axis: str, rows: int, cols: int,
                     dtype=np.float32, *, verify: str = "warn",
                     name: str = "st_a2a") -> CollectiveMatmul:
    """Tiled all-to-all (MoE dispatch building block) as an ST program.

    ``rows`` is the GLOBAL row count (local rows = rows/n, split into n
    blocks of rows/n² — the `lax.all_to_all(tiled=True)` layout).
    """
    n = dict(mesh.shape)[axis]
    if rows % (n * n):
        raise QueueError(f"rows ({rows}) must divide by axis size² {n * n}")
    q = CollectiveQueue(mesh, name)
    q.buffer("x", (rows, cols), dtype, pspec=(axis,))
    q.buffer("out", (rows, cols), dtype, pspec=(axis,))
    q.enqueue_all_to_all("x", "out", axis)
    prog = q.build(verify=verify)

    from . import overlap
    from jax.sharding import PartitionSpec as P
    reference = _smap_ref(
        mesh, lambda x: overlap.all_to_all_ppermute(x, axis),
        (P(axis),), P(axis))
    stock = _smap_ref(
        mesh,
        lambda x: jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                     tiled=True),
        (P(axis),), P(axis))
    return CollectiveMatmul(prog, ("x",), "out", reference, stock)


def build_tp_block(mesh, axis: str, m: int, k: int, f: int,
                   dtype=np.float32, *, bidirectional: bool = False,
                   chain: bool = False, verify: str = "warn",
                   name: str = "st_tp_block") -> CollectiveMatmul:
    """The headline "transformer block as ST schedule": a Megatron MLP
    with sequence parallelism, entirely as one ST program.

    ``x`` row-sharded [m, k] → all-gather-matmul with column-sharded
    ``w1`` [k, f] → relu → matmul-reduce-scatter with row-sharded
    ``w2`` [f, k] → ``out`` row-sharded [m, k].  The relu rides inside
    the all-gather's per-chunk compute hook (bit-exact nonlinearity),
    and every ring step of both collectives is a trigger→wait channel —
    so the whole block coalesces, prices, tunes, composes, and runs
    persistent like any other ST program.

    Reference: the stock shard_map lowering
    ``psum_scatter(relu(all_gather(x) @ w1) @ w2)``.

    ``chain=True`` appends a feedback kernel (``x = out`` — both are
    row-sharded [m, k]) so ``program.persistent(N)`` computes the
    N-deep chain ``x_{i+1} = block(x_i)`` in ONE dispatch — the
    "transformer stack as ST schedule" the overlap bench gates.
    """
    n = dict(mesh.shape)[axis]
    if m % n or f % n:
        raise QueueError(f"m ({m}) and f ({f}) must divide by axis size {n}")
    q = CollectiveQueue(mesh, name)
    q.buffer("x", (m, k), dtype, pspec=(axis,))
    q.buffer("w1", (k, f), dtype, pspec=(None, axis))
    q.buffer("w2", (f, k), dtype, pspec=(axis,))
    # h: full m rows of this rank's f/n hidden columns
    q.buffer("h", (n * m, f // n), dtype, pspec=(axis,))
    # y: per-rank full partials of the down-projection (rows = m each)
    q.buffer("y", (n * m, k), dtype, pspec=(axis,))
    q.buffer("out", (m, k), dtype, pspec=(axis,))
    q.enqueue_all_gather(
        "x", "h", axis,
        compute=lambda chunk, w1: jnp.maximum(chunk @ w1, 0.0),
        reads=("w1",), bidirectional=bidirectional)
    q.enqueue_compute(lambda h, w2: h @ w2, reads=("h", "w2"), writes=("y",),
                      name="down_proj")
    q.enqueue_reduce_scatter("y", "out", axis, tag_base=100)
    if chain:
        # persistent iterations re-run the whole descriptor walk, but
        # x has been rotated n-1 hops in place by the gather ring —
        # feeding out back in both restores a defined x AND makes the
        # persistent program the N-layer chain x_{i+1} = block(x_i)
        q.enqueue_compute(lambda o: o, reads=("out",), writes=("x",),
                          name="feedback")
    prog = q.build(verify=verify)

    from . import overlap

    def ref_decomposed(x, w1, w2):
        # bitwise oracle: relu commutes with the chunk deposits, and
        # the ring reduce-scatter repeats the ST accumulate order
        h = jnp.maximum(overlap.all_gather_matmul(x, w1, axis), 0.0)
        return overlap.reduce_scatter_ring(h @ w2, axis)

    def ref_stock(x, w1, w2):
        h = jnp.maximum(
            jax.lax.all_gather(x, axis, axis=0, tiled=True) @ w1, 0.0)
        return jax.lax.psum_scatter(h @ w2, axis, scatter_dimension=0,
                                    tiled=True)

    from jax.sharding import PartitionSpec as P
    specs = ((P(axis), P(None, axis), P(axis)), P(axis))
    reference = _smap_ref(mesh, ref_decomposed, *specs)
    stock = _smap_ref(mesh, ref_stock, *specs)
    return CollectiveMatmul(prog, ("x", "w1", "w2"), "out", reference, stock)
