"""Fused ST engine — the TPU-native stream-triggered execution path.

Executes an :class:`~repro.core.queue.STProgram` as **one** XLA
computation: every enqueued kernel, trigger, channel and wait lowers
into a single ``jax.jit(shard_map(...))`` program.  The host dispatches
once per program (vs once per descriptor in
:mod:`~repro.core.engine_host`), which is the paper's control-path
offload: after enqueue, the device sequencer drives kernels and
communication with no host round-trips.

Lowering of each descriptor kind
--------------------------------
* ``KernelDesc``      — apply ``fn`` to local buffer views.
* ``StartDesc``       — *writeValue*: bump the trigger token, after tying
                        it to everything the stream has produced so far
                        (stream order: a writeValue executes only after
                        all earlier stream commands complete).
* matched channels    — ``jax.lax.ppermute`` whose operand is *tied* to
                        the trigger token (the DWQ descriptor fires when
                        the counter hits its threshold).
* ``CollDesc``        — a whole deferred collective (beyond-paper).
* ``WaitDesc``        — *waitValue*: derive the completion counter from
                        the channel results and *gate* the stream on it.

Modes
-----
``stream``  (paper-faithful) — literal GPU-stream FIFO: the trigger
    depends on **all** prior stream commands and the wait gates **all**
    buffers, exactly like a stream-wide waitValue.
``dataflow`` (beyond-paper) — the trigger depends only on the buffers
    the batch actually sends, and the wait gates only the buffers the
    batch received into.  XLA may overlap independent kernels with
    communication — the scheduling freedom the paper's NIC offload was
    reaching for, recovered at compile time.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import counters
from .descriptors import (
    CollDesc,
    GridOffsetPeer,
    KernelDesc,
    OffsetPeer,
    PairListPeer,
    StartDesc,
    WaitDesc,
    perm_for,
)
from .matching import Channel
from .queue import STProgram


def _axes_tuple(axis) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _ensure_vma(x, axis_names: Tuple[str, ...]):
    """Make `x` explicitly varying over `axis_names` (shard_map tracks a
    "varying manual axes" set; constants must be cast to varying)."""
    cur = jax.typeof(x).vma
    missing = tuple(a for a in axis_names if a not in cur)
    if missing:
        x = jax.lax.pcast(x, missing, to="varying")
    return x


def _linear_rank(axes: Tuple[str, ...], mesh_shape: Dict[str, int]):
    """Flattened rank index over an ordered tuple of mesh axes."""
    idx = jnp.zeros((), dtype=jnp.int32)
    for a in axes:
        idx = idx * mesh_shape[a] + jax.lax.axis_index(a)
    return idx


def _is_full_identity(perm, axes: Tuple[str, ...],
                      mesh_shape: Dict[str, int]) -> bool:
    """True iff ``perm`` maps EVERY rank along ``axes`` to itself.

    Such a ppermute returns its operand bit-for-bit on every rank, so
    the collective can be elided — the payload is already in place.
    (A *partial* identity does not qualify: unmatched ranks would have
    received zeros, so the ppermute still changes data.)  Identity
    channels are how a part's own ghost planes ride the trigger/wait
    machinery (``GridOffsetPeer(axes, (0,..,0))``); eliding the
    collective keeps their counter semantics while costing only the
    pack/deposit copies.
    """
    n = 1
    for a in axes:
        n *= mesh_shape[a]
    return len(perm) == n and all(s == d for s, d in perm)


class FusedEngine:
    """Compile & run an STProgram as one fused XLA program."""

    def __init__(
        self,
        program: STProgram,
        mode: str = "stream",
        donate: bool = False,
        coalesce: bool = True,
        sanitize: bool = False,
    ):
        if mode not in ("stream", "dataflow"):
            raise ValueError("mode must be 'stream' or 'dataflow'")
        program.require_closed()
        self.program = program
        self.mode = mode
        self.donate = donate
        # Execute the batches' recorded coalescing plans (fused by-axis
        # transfers) when present; False forces the per-channel lowering
        # even on a plan-carrying program (A/B benchmarks, parity tests).
        self.coalesce = coalesce
        # Runtime sanitizer (see repro.core.verify): NaN-canary poisoning
        # of unwritten message slots + deposit-before-wait assertions
        # inside the interpreter (SanitizeError at trace time).
        self.sanitize = sanitize
        self.mesh = program.mesh
        self._mesh_shape = dict(self.mesh.shape)
        self._jitted = None
        # HostStats-shaped dispatch accounting (one dispatch per call,
        # zero host sync points) so benchmarks measure rather than infer
        from .engine_host import HostStats
        self.stats = HostStats()

    # -- public API -----------------------------------------------------------

    def shardings(self) -> Dict[str, NamedSharding]:
        return {
            name: NamedSharding(self.mesh, P(*spec.pspec))
            for name, spec in self.program.buffers.items()
        }

    def init_buffers(self, init: Optional[Dict[str, Any]] = None) -> Dict[str, jax.Array]:
        """Place the program's buffers, each shard straight on its device.

        A host value is split on the host and each shard copied to its
        own device; a device array is resharded; the other buffers are
        zeros made in place by one program with the buffers' shardings.
        No global array is staged whole on one device first.
        """
        init = init or {}
        shardings = self.shardings()
        buffers = self.program.buffers
        out = {}
        for name, value in init.items():
            dtype = buffers[name].dtype
            value = (value.astype(dtype) if isinstance(value, jax.Array)
                     else np.asarray(value, dtype))
            out[name] = jax.device_put(value, shardings[name])
        zeros = {n: s for n, s in buffers.items() if n not in init}
        if zeros:
            out.update(jax.jit(
                lambda: {n: jnp.zeros(s.shape, s.dtype)
                         for n, s in zeros.items()},
                out_shardings={n: shardings[n] for n in zeros})())
        return {n: out[n] for n in buffers}

    def compile(self):
        if self._jitted is None:
            self._jitted = self._build_jit()
        return self._jitted

    def __call__(self, mem: Dict[str, jax.Array]):
        out = self.compile()(mem)
        self.stats.dispatches += 1
        return out

    def lower(self, mem_specs: Optional[Dict[str, jax.ShapeDtypeStruct]] = None):
        """Lower (ShapeDtypeStruct stand-ins — used by dry-run/benchmarks)."""
        if mem_specs is None:
            shardings = self.shardings()
            mem_specs = {
                n: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shardings[n])
                for n, s in self.program.buffers.items()
            }
        return self.compile().lower(mem_specs)

    # -- lowering ---------------------------------------------------------------

    def _build_jit(self):
        prog = self.program
        specs = {n: P(*s.pspec) for n, s in prog.buffers.items()}

        body = functools.partial(_run_program, prog=prog, mode=self.mode,
                                 mesh_shape=self._mesh_shape,
                                 coalesce=self.coalesce,
                                 sanitize=self.sanitize)
        # check_vma=False: Pallas calls inside the program can't declare
        # varying-mesh-axes on their out_shapes; ordering is enforced by
        # the token ties, not by vma tracking.
        sharded = jax.shard_map(
            body, mesh=self.mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False,
        )
        donate = (0,) if self.donate else ()
        return jax.jit(sharded, donate_argnums=donate)


# -- program interpreter (runs inside shard_map, traced once) ----------------


def _run_program(mem: Dict[str, jax.Array], *, prog: STProgram, mode: str,
                 mesh_shape: Dict[str, int],
                 coalesce: bool = True,
                 sanitize: bool = False) -> Dict[str, jax.Array]:
    mem, _, _ = _interpret_program(mem, prog=prog, mode=mode,
                                   mesh_shape=mesh_shape, coalesce=coalesce,
                                   sanitize=sanitize)
    return mem


def fresh_token_banks(prog: STProgram):
    """One (trigger, completion) counter pair per program id — a single
    entry for a plain program, one per sub-program for a composed
    :class:`~repro.core.schedule.STSchedule` (each MPIX_Queue keeps its
    own counters; composition must not merge them)."""
    pids = tuple(prog.buffers_by_pid())
    return ({pid: counters.fresh_token() for pid in pids},
            {pid: counters.fresh_token() for pid in pids})


def _scope_name(d) -> str:
    """The named scope a descriptor lowers in: a kernel's queue op name,
    ``wait`` for a wait's gate, ``exchange`` for a batch's transfers."""
    if isinstance(d, KernelDesc):
        return d.name
    return "wait" if isinstance(d, WaitDesc) else "exchange"


def _interpret_program(
    mem: Dict[str, jax.Array],
    *,
    prog: STProgram,
    mode: str,
    mesh_shape: Dict[str, int],
    tokens: Optional[Dict[int, jax.Array]] = None,
    comp_tokens: Optional[Dict[int, jax.Array]] = None,
    coalesce: bool = True,
    sanitize: bool = False,
) -> Tuple[Dict[str, jax.Array], Dict[int, jax.Array], Dict[int, jax.Array]]:
    """Interpret one pass over ``prog``'s descriptors.

    Shared by :class:`FusedEngine` (one pass per host dispatch) and
    :class:`~repro.core.engine_persistent.PersistentEngine` (N passes
    inside a device-resident loop).  ``tokens``/``comp_tokens`` are the
    trigger and completion counter *banks*, keyed by program id: a plain
    program uses the single pid-0 pair; a composed schedule gets one
    pair per sub-program, so each queue's FIFO/gating is scoped to its
    own buffers and queues never serialize each other.  Passing the
    banks returned by a previous pass preserves MPIX_Queue-reuse
    semantics — the counters keep advancing across iterations instead
    of restarting at zero.

    With ``coalesce`` (default) a batch that carries a build-time
    :class:`~repro.core.matching.CoalescePlan` fires its fused by-axis
    transfers instead of one ppermute per channel; deposits replay in
    the original channel order so results are bit-identical either way.

    ``sanitize`` turns on the runtime sanitizer (see
    :mod:`repro.core.verify`): message-slot buffers are poisoned with
    NaN canaries at pass start — a read before the slot's deposit lands
    surfaces as NaNs instead of silently-stale data — and a
    :class:`~repro.core.verify.DepositTracker` asserts deposit-before-
    wait ordering as the interpreter traces, raising
    :class:`~repro.core.verify.SanitizeError` before any device work
    runs.  Race-free programs stay bit-identical: the canary's original
    value is saved and non-receiving ranks of the slot's first replace
    deposit restore it (later deposits see post-deposit contents, so
    only the first needs the fallback).
    """
    mem = dict(mem)
    if sanitize:
        from .verify import DepositTracker, canary_buffers
        tracker: Optional[DepositTracker] = DepositTracker(prog)
        canary_saved: Optional[Dict[str, jax.Array]] = {}
        for cb in canary_buffers(prog):
            if cb in mem:
                canary_saved[cb] = mem[cb]
                mem[cb] = jnp.full_like(mem[cb], jnp.nan)
    else:
        tracker = None
        canary_saved = None
    pid_bufs = prog.buffers_by_pid()
    if tokens is None or comp_tokens is None:
        fresh_trigs, fresh_comps = fresh_token_banks(prog)
        tokens = fresh_trigs if tokens is None else tokens
        comp_tokens = fresh_comps if comp_tokens is None else comp_tokens
    tokens = dict(tokens)
    comp_tokens = dict(comp_tokens)
    batches_by_index = {b.index: b for b in prog.batches}
    # buffers each batch received into (for dataflow-mode waits): a
    # cross-program channel's deposit is gated by the RECEIVING batch's
    # wait (cross_recv_bufs), not by the triggering batch's own wait
    recv_bufs_by_batch: Dict[int, List[str]] = {
        b.index: [c.dst_buf for c in b.channels
                  if c.dst_pid is None or c.dst_pid == b.pid]
        + [c.out for c in b.colls] + list(b.cross_recv_bufs)
        for b in prog.batches
    }
    send_bufs_by_batch: Dict[int, List[str]] = {
        b.index: [c.src_buf for c in b.channels] + [c.buf for c in b.colls]
        for b in prog.batches
    }

    for d in prog.descriptors:
        pid = d.pid
        # the descriptor's ops carry its queue op name in their HLO
        # op_name metadata, so a device trace can name the stage
        # that took the time; a scope changes no op
        with jax.named_scope(_scope_name(d)):
            if isinstance(d, KernelDesc):
                if tracker is not None:
                    tracker.kernel(d)
                args = [mem[r] for r in d.reads]
                if mode == "stream":
                    # strict FIFO: kernel ordered after everything before it
                    # on its OWN program's stream (queues stay independent)
                    tokens[pid], args = counters.tie(tokens[pid], *args)
                outs = d.fn(*args)
                if not isinstance(outs, (tuple, list)):
                    outs = (outs,)
                if len(outs) != len(d.writes):
                    raise ValueError(
                        f"kernel {d.name!r} returned {len(outs)} values for "
                        f"{len(d.writes)} write buffers"
                    )
                for w, o in zip(d.writes, outs):
                    spec = prog.buffers[w].pspec
                    axes = tuple(a for a in jax.tree.leaves(list(spec)) if a)
                    mem[w] = _ensure_vma(o.astype(prog.buffers[w].dtype), axes)
                    if canary_saved:
                        canary_saved.pop(w, None)  # whole-buffer rewrite
                if mode == "stream":
                    tokens[pid] = counters.completion_from(
                        tokens[pid], *[mem[w] for w in d.writes])

            elif isinstance(d, StartDesc):
                if tracker is not None:
                    tracker.start(d)
                batch = batches_by_index[d.batch]
                use_plan = coalesce and batch.plan is not None
                # writeValue: bump after all earlier commands of THIS
                # program's stream.
                if mode == "stream":
                    deps = [mem[b] for b in pid_bufs[pid]]
                    tokens[pid], _ = counters.tie(tokens[pid], *deps)
                elif not use_plan:
                    deps = [mem[b] for b in send_bufs_by_batch[d.batch]]
                    tokens[pid], _ = counters.tie(tokens[pid], *deps)
                # else (dataflow + coalesced): the trigger ties only to the
                # packed staging buffers, inside _run_coalesced_batch — the
                # pack already depends on every source slab, so tying the
                # whole live set would just re-materialize untouched buffers
                tokens[pid] = counters.bump(tokens[pid])
                # fire every descriptor in the batch (threshold reached).
                # Completion is banked per DESTINATION program: a
                # cross-program channel bumps the receiver's completion
                # counter, so the receiver's wait gate observes this
                # sender's completion (trigger stays on the sender's bank).
                results_by_pid: Dict[int, List[Any]] = {}
                if use_plan:
                    plan = batch.plan
                    mem, received = _run_coalesced_batch(mem, plan, tokens[pid],
                                                         mesh_shape,
                                                         fallbacks=canary_saved)
                    # a fused transfer feeds the completion counter of every
                    # program it carries a final segment for (the deposited
                    # slabs are slices of the payload, so gating on the
                    # payload gates the deposits — and an all-domestic batch
                    # keeps the exact PR-4 graph: one barrier, all payloads)
                    pid_transfers: Dict[int, List[int]] = {}
                    for ci, ch in enumerate(plan.channels):
                        if not plan.routes[ci]:
                            continue  # statically dead: deposits zeros only
                        dpid = pid if ch.dst_pid is None else ch.dst_pid
                        ti = plan.routes[ci][-1][0]
                        pid_transfers.setdefault(dpid, []).append(ti)
                    for dpid, tis in pid_transfers.items():
                        results_by_pid[dpid] = [received[ti]
                                                for ti in sorted(set(tis))]
                else:
                    for ch in batch.channels:
                        mem, r = _run_channel(mem, ch, tokens[pid], mesh_shape,
                                              fallbacks=canary_saved)
                        dpid = pid if ch.dst_pid is None else ch.dst_pid
                        results_by_pid.setdefault(dpid, []).append(r)
                for coll in batch.colls:
                    mem, r = _run_collective(mem, coll, tokens[pid], prog)
                    if canary_saved:
                        canary_saved.pop(coll.out, None)  # wholly overwritten
                    results_by_pid.setdefault(pid, []).append(r)
                for dpid, rs in results_by_pid.items():
                    comp_tokens[dpid] = counters.completion_from(
                        comp_tokens[dpid], *rs)

            elif isinstance(d, WaitDesc):
                if tracker is not None:
                    tracker.wait(d)
                # waitValue: gate this program's stream on its completion
                # counter (another program's descriptors flow right past).
                if mode == "stream":
                    names = list(pid_bufs[pid])
                    comp_tokens[pid], vals = counters.gate(
                        comp_tokens[pid], *[mem[n] for n in names])
                    mem.update(zip(names, vals))
                    tokens[pid] = (counters.bump(tokens[pid], 0)
                                   + 0 * comp_tokens[pid])  # stream advances
                else:
                    names = recv_bufs_by_batch.get(d.batch, [])
                    if names:
                        comp_tokens[pid], vals = counters.gate(
                            comp_tokens[pid], *[mem[n] for n in names])
                        mem.update(zip(names, vals))
            # Send/Recv/Coll descs themselves are no-ops here: they were
            # matched into their batch at build time (deferred execution).

    return mem, tokens, comp_tokens


def _deposit_channel(mem, ch: Channel, received, mesh_shape,
                     fallbacks: Optional[Dict[str, jax.Array]] = None):
    """Deposit one channel's received slab into its destination buffer.

    Shared by the per-channel and coalesced lowerings (same ops, same
    order → bit-identical results).  The receiver mask always derives
    from the channel's *original* peer permutation, independent of how
    the payload travelled.

    ``fallbacks`` is the sanitizer's saved-original map: when the
    destination buffer was NaN-poisoned at pass start, the first
    replace deposit takes its non-receiver lanes from the saved
    original instead of the poisoned current value (consumed on use, so
    later deposits see real post-deposit contents).
    """
    axes = _axes_tuple(ch.axis)
    perm = ch.perm(mesh_shape)
    dst = mem[ch.dst_buf]
    region = ch.recv_region if ch.recv_region is not None else tuple(
        slice(None) for _ in dst.shape
    )
    if ch.mode == "add":
        # unmatched receivers got zeros from ppermute — neutral for add
        dst = dst.at[region].add(received.astype(dst.dtype))
    else:
        # only ranks that actually have a matching sender take the value
        dsts = np.array(sorted({d for _, d in perm}), dtype=np.int32)
        me = _linear_rank(axes, mesh_shape)
        is_receiver = jnp.isin(me, jnp.asarray(dsts))
        orig = fallbacks.pop(ch.dst_buf, None) if fallbacks else None
        cur = dst[region] if orig is None else orig[region]
        dst = dst.at[region].set(
            jnp.where(is_receiver, received.astype(dst.dtype), cur)
        )
    mem[ch.dst_buf] = dst
    return mem


def _run_channel(mem, ch: Channel, token, mesh_shape, fallbacks=None):
    """One matched (send, recv) pair → one ppermute, tied to the trigger."""
    axes = _axes_tuple(ch.axis)
    src = mem[ch.src_buf]
    if ch.send_region is not None:
        src = src[ch.send_region]
    # DWQ deferred execution: operand depends on the trigger counter.
    _, (src,) = counters.tie(token, src)
    perm = ch.perm(mesh_shape)
    if _is_full_identity(perm, axes, mesh_shape):
        received = src  # every rank keeps its payload: collective elided
    else:
        received = jax.lax.ppermute(
            src, axes if len(axes) > 1 else axes[0], perm)
    mem = _deposit_channel(mem, ch, received, mesh_shape, fallbacks=fallbacks)
    return mem, received


def _run_coalesced_batch(mem, plan, token, mesh_shape, fallbacks=None):
    """Fire one batch's coalescing plan: fused by-axis transfers.

    Stage by stage, each :class:`~repro.core.matching.CoalescedChannel`
    packs its member slabs (first hop) and relayed payloads (later
    hops) into ONE contiguous staging buffer at static offsets — the
    paper's contiguous MPI buffer — ties it to the trigger counter, and
    moves it with ONE single-axis ``ppermute``.  Because relays copy
    payloads verbatim and an axis-ordered route exists iff the direct
    source rank exists, each channel's final segment is bit-identical
    to its direct multi-axis ppermute; deposits then replay in original
    channel order.

    Returns ``(mem, received)`` with one payload per fused transfer;
    the caller banks each destination program's completion on the
    transfers that carry its final segments (see the StartDesc
    handling in :func:`_interpret_program`).
    """
    received = []
    for t in plan.transfers:
        parts = []
        for seg in t.segments:
            if seg.hop == 0:
                ch = plan.channels[seg.channel]
                src = mem[ch.src_buf]
                if ch.send_region is not None:
                    src = src[ch.send_region]
                parts.append(src.reshape(-1))
            else:  # relay: verbatim copy out of the previous hop's buffer
                pt, po = plan.routes[seg.channel][seg.hop - 1]
                parts.append(
                    jax.lax.slice_in_dim(received[pt], po, po + seg.size))
        staged = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        # DWQ deferred execution: ONE tie for the whole fused transfer.
        _, (staged,) = counters.tie(token, staged)
        if _is_full_identity(t.perm, _axes_tuple(t.axis), mesh_shape):
            received.append(staged)  # full identity: collective elided
        else:
            received.append(jax.lax.ppermute(staged, t.axis, t.perm))

    for ci, ch in enumerate(plan.channels):
        route = plan.routes[ci]
        if not route:
            # statically dead channel: its ppermute would deliver zeros
            # on every rank — deposit them without packing or moving
            seg = jnp.zeros(plan.shapes[ci], mem[ch.src_buf].dtype)
            mem = _deposit_channel(mem, ch, seg, mesh_shape,
                                   fallbacks=fallbacks)
            continue
        ti, off = route[-1]
        size = int(np.prod(plan.shapes[ci], dtype=np.int64))
        seg = jax.lax.slice_in_dim(received[ti], off, off + size)
        mem = _deposit_channel(mem, ch, seg.reshape(plan.shapes[ci]),
                               mesh_shape, fallbacks=fallbacks)
    return mem, received


def _run_collective(mem, coll: CollDesc, token, prog: STProgram):
    axes = _axes_tuple(coll.axis)
    axis = axes if len(axes) > 1 else axes[0]
    x = mem[coll.buf]
    _, (x,) = counters.tie(token, x)
    kw = dict(coll.kwargs)
    if coll.op == "all_gather":
        out = jax.lax.all_gather(x, axis, axis=kw.get("dim", 0), tiled=kw.get("tiled", True))
    elif coll.op == "reduce_scatter":
        out = jax.lax.psum_scatter(x, axis, scatter_dimension=kw.get("dim", 0), tiled=kw.get("tiled", True))
    elif coll.op == "all_reduce":
        out = jax.lax.psum(x, axis)
    elif coll.op == "all_to_all":
        out = jax.lax.all_to_all(x, axis, split_axis=kw.get("split_axis", 0),
                                 concat_axis=kw.get("concat_axis", 0), tiled=kw.get("tiled", True))
    elif coll.op == "ppermute":
        out = jax.lax.ppermute(x, axis, kw["perm"])
    else:  # pragma: no cover — validated at enqueue
        raise ValueError(coll.op)
    spec = prog.buffers[coll.out].pspec
    out_axes = tuple(a for a in jax.tree.leaves(list(spec)) if a)
    mem[coll.out] = _ensure_vma(out.astype(prog.buffers[coll.out].dtype), out_axes)
    return mem, out
