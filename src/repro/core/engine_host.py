"""Host-orchestrated engine — the paper's *baseline* control path (Fig. 1)
and its progress-thread emulation cost model.

Executes the same :class:`~repro.core.queue.STProgram` as the fused
engine, but the way a conventional GPU-aware MPI application does:

* every compute kernel is its **own** device dispatch;
* the host **synchronizes** with the device at kernel boundaries
  (``block_until_ready`` — the "expensive synchronization points" of
  paper Fig. 1);
* each communication batch is dispatched as separate per-channel device
  programs, again host-driven — the analogue of the CPU progress thread
  walking descriptors and posting them one at a time (paper §IV-B).

The engine counts dispatches and host sync points so benchmarks can
report the control-path cost next to wall time.  Results are bit-wise
comparable with the fused engine (tests assert allclose), so the A/B is
purely a control-path experiment — exactly the paper's methodology.

Sync policies
-------------
``every_op``  — block after *every* dispatch (paper Fig. 1 behaviour).
``batch``     — block once per communication batch (an optimistic host
                baseline: a perfectly pipelining CPU progress thread).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .descriptors import CollDesc, KernelDesc, StartDesc, WaitDesc
from .engine_fused import FusedEngine, _axes_tuple, _ensure_vma, _linear_rank
from .matching import Channel
from .queue import STProgram


@dataclasses.dataclass
class HostStats:
    dispatches: int = 0
    sync_points: int = 0

    def reset(self):
        self.dispatches = 0
        self.sync_points = 0


class HostEngine:
    """Per-descriptor, host-driven execution of an STProgram."""

    def __init__(self, program: STProgram, sync: str = "every_op",
                 sanitize: bool = False):
        if sync not in ("every_op", "batch"):
            raise ValueError("sync must be 'every_op' or 'batch'")
        program.require_closed()
        if sanitize:
            # the host engine syncs at descriptor boundaries, so there is
            # no canary to plant — the sanitizer reduces to the static
            # deposit-before-wait assertion over the descriptor stream
            from .verify import check_deposit_order
            check_deposit_order(program)
        self.program = program
        self.sync = sync
        self.mesh = program.mesh
        self._mesh_shape = dict(self.mesh.shape)
        self.stats = HostStats()
        self._kernel_cache: Dict[int, Any] = {}
        self._channel_cache: Dict[int, Any] = {}
        self._coll_cache: Dict[int, Any] = {}

    # -- buffers (same layout as the fused engine) ----------------------------

    shardings = FusedEngine.shardings
    init_buffers = FusedEngine.init_buffers

    # -- execution ------------------------------------------------------------

    def __call__(self, mem: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        prog = self.program
        mem = dict(mem)
        batches = {b.index: b for b in prog.batches}

        for i, d in enumerate(prog.descriptors):
            if isinstance(d, KernelDesc):
                fn = self._kernel_fn(i, d)
                outs = fn(*[mem[r] for r in d.reads])
                if not isinstance(outs, (tuple, list)):
                    outs = (outs,)
                mem.update(zip(d.writes, outs))
                self.stats.dispatches += 1
                self._sync(outs, always=False)

            elif isinstance(d, StartDesc):
                # The "progress thread" observes the trigger and posts each
                # descriptor of the batch as its own device program.
                batch = batches[d.batch]
                results = []
                for j, ch in enumerate(batch.channels):
                    fn = self._channel_fn((i, j), ch)
                    mem[ch.dst_buf], r = fn(mem[ch.src_buf], mem[ch.dst_buf])
                    results.append(r)
                    self.stats.dispatches += 1
                    self._sync([r], always=False)
                for j, coll in enumerate(batch.colls):
                    fn = self._coll_fn((i, j), coll)
                    mem[coll.out] = fn(mem[coll.buf])
                    results.append(mem[coll.out])
                    self.stats.dispatches += 1
                    self._sync([mem[coll.out]], always=False)
                if self.sync == "batch" and results:
                    jax.block_until_ready(results)
                    self.stats.sync_points += 1

            elif isinstance(d, WaitDesc):
                # Host-level MPI_Waitall: a true host block.
                jax.block_until_ready(list(mem.values()))
                self.stats.sync_points += 1

        return mem

    # -- per-descriptor compiled programs --------------------------------------

    def _sync(self, vals, always: bool):
        if always or self.sync == "every_op":
            jax.block_until_ready(list(vals))
            self.stats.sync_points += 1

    def _kernel_fn(self, key: int, d: KernelDesc):
        if key not in self._kernel_cache:
            prog = self.program
            in_specs = tuple(P(*prog.buffers[r].pspec) for r in d.reads)
            out_specs = tuple(P(*prog.buffers[w].pspec) for w in d.writes)

            def body(*args):
                outs = d.fn(*args)
                if not isinstance(outs, (tuple, list)):
                    outs = (outs,)
                fixed = []
                for w, o in zip(d.writes, outs):
                    axes = tuple(a for a in jax.tree.leaves(list(prog.buffers[w].pspec)) if a)
                    fixed.append(_ensure_vma(o.astype(prog.buffers[w].dtype), axes))
                return tuple(fixed)

            self._kernel_cache[key] = jax.jit(
                jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False)
            )
        return self._kernel_cache[key]

    def _channel_fn(self, key, ch: Channel):
        if key not in self._channel_cache:
            prog = self.program
            mesh_shape = self._mesh_shape
            axes = _axes_tuple(ch.axis)
            src_spec = P(*prog.buffers[ch.src_buf].pspec)
            dst_spec = P(*prog.buffers[ch.dst_buf].pspec)
            perm = ch.perm(mesh_shape)

            def body(src, dst):
                s = src[ch.send_region] if ch.send_region is not None else src
                received = jax.lax.ppermute(
                    s, axes if len(axes) > 1 else axes[0], perm
                )
                region = ch.recv_region if ch.recv_region is not None else tuple(
                    slice(None) for _ in dst.shape
                )
                if ch.mode == "add":
                    dst = dst.at[region].add(received.astype(dst.dtype))
                else:
                    dsts = np.array(sorted({t for _, t in perm}), dtype=np.int32)
                    me = _linear_rank(axes, mesh_shape)
                    is_recv = jnp.isin(me, jnp.asarray(dsts))
                    dst = dst.at[region].set(
                        jnp.where(is_recv, received.astype(dst.dtype), dst[region])
                    )
                return dst, received

            self._channel_cache[key] = jax.jit(
                jax.shard_map(body, mesh=self.mesh,
                              in_specs=(src_spec, dst_spec),
                              out_specs=(dst_spec, src_spec), check_vma=False)
            )
        return self._channel_cache[key]

    def _coll_fn(self, key, coll: CollDesc):
        if key not in self._coll_cache:
            prog = self.program
            axes = _axes_tuple(coll.axis)
            axis = axes if len(axes) > 1 else axes[0]
            in_spec = P(*prog.buffers[coll.buf].pspec)
            out_spec = P(*prog.buffers[coll.out].pspec)
            kw = dict(coll.kwargs)

            def body(x):
                if coll.op == "all_gather":
                    out = jax.lax.all_gather(x, axis, axis=kw.get("dim", 0),
                                             tiled=kw.get("tiled", True))
                elif coll.op == "reduce_scatter":
                    out = jax.lax.psum_scatter(x, axis,
                                               scatter_dimension=kw.get("dim", 0),
                                               tiled=kw.get("tiled", True))
                elif coll.op == "all_reduce":
                    out = jax.lax.psum(x, axis)
                elif coll.op == "all_to_all":
                    out = jax.lax.all_to_all(x, axis, split_axis=kw.get("split_axis", 0),
                                             concat_axis=kw.get("concat_axis", 0),
                                             tiled=kw.get("tiled", True))
                elif coll.op == "ppermute":
                    out = jax.lax.ppermute(x, axis, kw["perm"])
                else:  # pragma: no cover
                    raise ValueError(coll.op)
                out_axes = tuple(a for a in jax.tree.leaves(list(prog.buffers[coll.out].pspec)) if a)
                return _ensure_vma(out.astype(prog.buffers[coll.out].dtype), out_axes)

            self._coll_cache[key] = jax.jit(
                jax.shard_map(body, mesh=self.mesh, in_specs=(in_spec,),
                              out_specs=out_spec, check_vma=False)
            )
        return self._coll_cache[key]
