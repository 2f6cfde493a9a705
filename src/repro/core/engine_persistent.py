"""Persistent ST engine — the device owns the iteration loop.

:class:`~repro.core.engine_fused.FusedEngine` offloads the control path
of one communication batch, but the *host* still re-dispatches the
program every iteration of a timed loop (N iterations → N dispatches).
The follow-up work on fully offloaded stream triggering moves the whole
loop onto the device: the host enqueues once, and a device-resident
sequencer re-runs trigger → communicate → wait → compute until the
iteration count (or a convergence predicate) says stop.

This engine is that execution model for an :class:`STProgram`: the
fused interpreter (:func:`~repro.core.engine_fused._interpret_program`)
runs inside an on-device ``jax.lax.fori_loop`` whose carry holds

* every program buffer (the Faces field ``u`` survives on-device across
  iterations — no host round-trip between them);
* the **trigger and completion counters**, threaded through every pass
  so the MPIX_Queue-reuse semantics of :mod:`.queue` hold literally:
  iteration i+1's thresholds sit above iteration i's counter values
  instead of restarting from zero;
* optionally a per-iteration scalar reduction (residual norms etc.), so
  convergence-style loops can report progress without a host sync.

Double buffering
----------------
In ``dataflow`` mode the wait gates only the buffers a batch received
into.  Message *slot* buffers (pure staging: packed faces out, received
faces in) are therefore the only serialization between iterations that
is not a real data dependency.  With ``double_buffer=True`` each slot
buffer gets two copies and iteration i uses copy ``i % 2``: combined
with ``unroll=2`` on the loop, iteration i+1's packs write slot B while
iteration i's waits still gate slot A, recovering the pack/wait overlap
a NIC-offloaded persistent queue gets from alternating DWQ entries.

The two copies are **zero-copy rotated**: the loop carry holds them as
separate ``(cur, alt)`` pytree leaves and each iteration returns
``(alt, written)`` — a pure reference swap.  No stacked ``[2, ...]``
slot arrays, no ``dynamic_update_index`` re-materialization per
iteration, and no parity arithmetic: after the loop the last write
always sits in the ``alt`` position (even under a predicate-terminated
``while_loop``, where the realized count is dynamic).

Slot safety is decided statically: a buffer is double-buffered only if
it is touched by a channel/collective and its first access in execution
order is a write (replace-mode deposits count as writes; add-mode
deposits accumulate across iterations and disqualify the buffer).

Convergence termination (``cond_fn`` / ``until``)
-------------------------------------------------
A convergence-style solver (the Nekbone/Faces regime) cannot know
``n_iters`` up front — the classic implementation round-trips a
residual to the host every iteration to decide when to stop, which is
exactly the host-in-the-control-path cost the ST model removes.  With
``cond_fn`` set (or ``STProgram.persistent(n, until=...)``), the fixed
``fori_loop`` becomes a ``jax.lax.while_loop``:

* each iteration evaluates ``reduce_fn`` (required) into a scalar and
  feeds it to ``cond_fn(reduction) -> bool``; the loop continues while
  the predicate holds (e.g. ``residual >= tol``), bounded by
  ``max_iters``.  The first iteration always runs (there is no
  reduction to test before it).
* double buffering needs no parity bookkeeping: the ``(cur, alt)``
  rotation leaves the last realized write in the ``alt`` carry position
  regardless of how many iterations the predicate allowed (a
  ``while_loop`` has no induction variable and no static unroll, but
  the rotation is induction-free anyway).
* ``__call__`` returns ``(mem, reductions, n_done)``: the reduction
  trace padded with zeros to ``max_iters`` plus the realized iteration
  count — still ONE host dispatch and zero host syncs until converged.

Multi-queue schedules (``STSchedule``)
--------------------------------------
A composed :class:`~repro.core.schedule.STSchedule` (see
:func:`repro.core.schedule.compose`) runs here too — N concurrent
queues' persistent loops fused into ONE host dispatch.  The loop carry
banks the trigger/completion counters *per program*, and per-program
iteration counts / termination predicates are honored by a masked
``while_loop``: each iteration interprets the whole interleaved
program, then a per-program *active* flag decides whether that
program's buffers (and slot copies) take the new values or stay frozen
at the program's own termination point.  The loop runs until every
program's predicate has terminated (bounded by the max per-program
count), and ``__call__`` returns per-program reduction traces and
realized iteration counts — the device-resident equivalent of N
independent ``run_until_converged`` loops, in one dispatch, with each
queue's communication overlapping the others' compute.  Per-program
reductions are supplied as ``reduce_fns={sub_name: fn}``; each fn sees
the full (namespaced) buffer dict but must only read its own program's
buffers — a frozen program's buffers hold their converged values, but
cross-program reads would still observe in-flight state.

Schedules with **cross-program channels** (``compose(..., links=...)``)
run here unchanged: the interpreter banks each deposit's completion on
the *receiving* program's counter, and the masked loop composes with
links naturally — when a link's peer has already converged (inactive),
its descriptors still execute each pass, so its packs keep publishing
its FROZEN boundary to the still-active neighbors (deposits into the
frozen program's own buffers are discarded by its mask).  Linked
neighbors therefore see a converged part as a constant boundary
condition, not stale in-flight data.

The same masked-loop idiom also runs at *per-sequence* grain: the
serving engine (:class:`repro.launch.serve.ServeEngine`) decodes a
batch of requests as one resident ``while_loop`` whose per-sequence
active flags freeze a finished request's cache position (EOS/budget/
capacity termination) exactly as the per-program flags here freeze a
converged program's buffers — with masked per-slot *re-admission*
(``Model.select_slots``) layered on top for continuous batching.

Dispatch accounting
-------------------
``stats`` is a :class:`~repro.core.engine_host.HostStats`: one call =
one dispatch, zero host sync points, regardless of ``n_iters`` (or of
how many iterations a ``cond_fn`` loop realizes) — the contrast
:mod:`benchmarks.faces_bench` reports against the host
(``n_iters × dispatch_count_host()``) and fused (``n_iters × 1``)
engines.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .descriptors import KernelDesc, StartDesc
from .engine_fused import FusedEngine, _interpret_program, fresh_token_banks
from .queue import STProgram
from .schedule import STSchedule


def slot_buffers(prog: STProgram) -> Tuple[str, ...]:
    """Statically identify message-slot buffers safe to double-buffer.

    A buffer qualifies when (a) a channel or collective touches it and
    (b) its first access in *execution* order is a write — so its value
    at iteration start never reaches the result.  Replace-mode channel
    deposits count as writes (non-receiving ranks preserve a value both
    slots share); add-mode deposits read the accumulator and disqualify.
    """
    comm_bufs: Set[str] = set()
    for b in prog.batches:
        for ch in b.channels:
            comm_bufs.add(ch.src_buf)
            comm_bufs.add(ch.dst_buf)
        for coll in b.colls:
            comm_bufs.add(coll.buf)
            comm_bufs.add(coll.out)

    first_access: Dict[str, str] = {}  # buffer -> "read" | "write"

    def see(buf: str, kind: str):
        first_access.setdefault(buf, kind)

    for d in prog.descriptors:
        if isinstance(d, KernelDesc):
            for r in d.reads:
                see(r, "read")
            for w in d.writes:
                see(w, "write")
        elif isinstance(d, StartDesc):
            batch = next(b for b in prog.batches if b.index == d.batch)
            for ch in batch.channels:
                see(ch.src_buf, "read")
            for coll in batch.colls:
                see(coll.buf, "read")
            for ch in batch.channels:
                see(ch.dst_buf, "read" if ch.mode == "add" else "write")
            for coll in batch.colls:
                see(coll.out, "write")

    return tuple(sorted(
        b for b in comm_bufs if first_access.get(b) == "write"
    ))


class PersistentEngine(FusedEngine):
    """Run an STProgram for ``n_iters`` iterations as ONE host dispatch.

    Inherits the buffer/compile surface (``shardings``, ``init_buffers``,
    ``compile``, ``lower``) from :class:`FusedEngine`; only the lowered
    body (the device-resident loop) and the dispatch accounting differ.

    Parameters
    ----------
    program:
        The matched program; ``program.n_iters`` (see
        :meth:`STProgram.persistent`) supplies the iteration count when
        ``n_iters`` is not given.
    n_iters:
        Device-resident iteration count (>= 1).  Values > 1 are subject
        to the same quiescence reuse-guard as ``STProgram.persistent``.
    mode:
        ``stream`` / ``dataflow`` — same ordering semantics as
        :class:`FusedEngine`, applied to every pass.
    double_buffer:
        Alternate message-slot copies between iterations (default: on in
        ``dataflow`` mode).  The loop is unrolled ×2 so consecutive
        iterations coexist in the loop body and XLA may overlap them.
    unroll:
        Explicit ``fori_loop`` unroll factor for the fixed-count
        persistent loop (a :mod:`repro.launch.tune` knob).  ``None``
        (default) derives it from ``double_buffer`` as above; the value
        never changes numerics, only how many iteration bodies XLA
        schedules together.
    reduce_fn:
        Optional ``fn(mem) -> scalar`` evaluated after every iteration
        *inside* the device loop (use ``jax.lax.psum`` over the mesh
        axes for a global value).  ``__call__`` then returns
        ``(mem, reductions)`` with ``reductions.shape == (n_iters,)`` —
        convergence traces without any host sync inside the loop.
        Required when ``cond_fn`` is set.
    cond_fn:
        Optional termination predicate ``fn(reduction) -> bool`` (e.g.
        ``lambda residual: residual >= tol``) evaluated on each
        iteration's reduction *inside* the device loop; the loop
        continues while it returns True, bounded by ``max_iters``.
        Defaults to ``program.until``.  ``__call__`` then returns
        ``(mem, reductions, n_done)`` with ``reductions`` zero-padded to
        ``max_iters`` and ``n_done`` the realized iteration count.
    max_iters:
        Safety bound for ``cond_fn`` loops (defaults to
        ``n_iters`` / ``program.n_iters``).  Only meaningful with a
        predicate.
    reduce_fns:
        Multi-queue only: per-sub-program reductions for a composed
        :class:`~repro.core.schedule.STSchedule`, keyed by sub-program
        name.  Required for every sub with an ``until`` predicate;
        optional for the rest (their traces are simply recorded).
        ``__call__`` then returns ``(mem, reductions, n_done)`` where
        ``reductions`` maps each reduced sub to its ``(max_iters,)``
        trace (zero-padded past the sub's realized count) and ``n_done``
        maps every sub to its realized iteration count.
    """

    def __init__(
        self,
        program: STProgram,
        n_iters: Optional[int] = None,
        mode: str = "stream",
        double_buffer: Optional[bool] = None,
        reduce_fn: Optional[Callable[[Dict[str, jax.Array]], jax.Array]] = None,
        cond_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
        max_iters: Optional[int] = None,
        reduce_fns: Optional[Dict[str, Callable]] = None,
        donate: bool = False,
        coalesce: bool = True,
        sanitize: bool = False,
        unroll: Optional[int] = None,
    ):
        super().__init__(program, mode=mode, donate=donate, coalesce=coalesce,
                         sanitize=sanitize)
        self.reduce_fns: Dict[str, Callable] = dict(reduce_fns or {})

        if isinstance(program, STSchedule):
            # composed multi-queue schedule: iteration counts and
            # predicates are per-program (set via .persistent on each
            # program before compose); the global-loop knobs make no
            # sense here.
            for arg, nm in ((n_iters, "n_iters"), (reduce_fn, "reduce_fn"),
                            (cond_fn, "cond_fn"), (max_iters, "max_iters")):
                if arg is not None:
                    raise ValueError(
                        f"{nm} does not apply to a composed STSchedule: "
                        "iteration counts/predicates are per-program "
                        "(program.persistent(...) before compose) and "
                        "reductions go through reduce_fns={name: fn}")
            names = {s.name for s in program.subs}
            for nm in self.reduce_fns:
                if nm not in names:
                    raise ValueError(
                        f"reduce_fns names unknown sub-program {nm!r} "
                        f"(have {sorted(names)})")
            for s in program.subs:
                if s.until is not None and s.name not in self.reduce_fns:
                    raise ValueError(
                        f"sub-program {s.name!r} has an until-predicate "
                        f"but no reduce_fns[{s.name!r}] to evaluate it on")
            self.cond_fn = None
            self.reduce_fn = None
            self.n_iters = self.max_iters = max(
                s.n_iters for s in program.subs)
            # the masked while path is needed whenever the subs diverge
            # (different counts or any predicate) or traces are wanted
            self._schedule_while = (
                bool(self.reduce_fns)
                or any(s.until is not None for s in program.subs)
                or len({s.n_iters for s in program.subs}) > 1
            )
        else:
            if self.reduce_fns:
                raise ValueError(
                    "reduce_fns is for composed STSchedules; a plain "
                    "program takes the single reduce_fn")
            self._schedule_while = False
            self.cond_fn = cond_fn if cond_fn is not None else program.until
            if max_iters is not None and self.cond_fn is None:
                raise ValueError(
                    "max_iters is only meaningful with cond_fn/until")
            if max_iters is None:
                max_iters = program.n_iters if n_iters is None else n_iters
            self.n_iters = self.max_iters = int(max_iters)
            if self.n_iters < 1:
                raise ValueError(f"n_iters must be >= 1, got {self.n_iters}")
            if self.cond_fn is not None and reduce_fn is None:
                raise ValueError(
                    "cond_fn requires reduce_fn: the termination predicate "
                    "is evaluated on the per-iteration scalar reduction")
            # an explicit n_iters/cond_fn override must pass the same
            # quiescence reuse-guard STProgram.persistent() enforces
            # (raises QueueError)
            program.persistent(self.n_iters, until=self.cond_fn)
            self.reduce_fn = reduce_fn
        self.double_buffer = (mode == "dataflow") if double_buffer is None \
            else bool(double_buffer)
        self._slots: Tuple[str, ...] = (
            slot_buffers(program) if self.double_buffer else ()
        )
        # persistent-loop unroll (fori_loop path only): default pairs
        # consecutive iterations exactly when double buffering gives
        # them independent slots; an explicit value is a tuner knob
        # (repro.launch.tune) — numerics are unaffected either way.
        if unroll is not None and int(unroll) < 1:
            raise ValueError(f"unroll must be >= 1, got {unroll}")
        self.unroll = None if unroll is None else int(unroll)

    def __call__(self, mem: Dict[str, jax.Array]):
        """One dispatch of the whole loop (counted by ``FusedEngine``),
        in the host span ``st.persistent.dispatch`` with its iteration
        bound as ``iters``; the span records only while a profiler runs."""
        with jax.profiler.TraceAnnotation("st.persistent.dispatch",
                                          iters=self.max_iters):
            return super().__call__(mem)

    # -- lowering -------------------------------------------------------------

    def _build_jit(self):
        prog = self.program
        specs = {n: P(*s.pspec) for n, s in prog.buffers.items()}

        if self._schedule_while:
            out_specs = (specs,
                         {nm: P() for nm in self.reduce_fns},
                         {s.name: P() for s in prog.subs})
            body = functools.partial(
                _run_schedule_while,
                sched=prog,
                mode=self.mode,
                mesh_shape=self._mesh_shape,
                slots=self._slots,
                reduce_fns=self.reduce_fns,
                coalesce=self.coalesce,
                sanitize=self.sanitize,
            )
        elif self.cond_fn is not None:
            out_specs = (specs, P(), P())
            body = functools.partial(
                _run_persistent_while,
                prog=prog,
                mode=self.mode,
                mesh_shape=self._mesh_shape,
                max_iters=self.max_iters,
                slots=self._slots,
                reduce_fn=self.reduce_fn,
                cond_fn=self.cond_fn,
                coalesce=self.coalesce,
                sanitize=self.sanitize,
            )
        else:
            out_specs = (specs, P()) if self.reduce_fn is not None else specs
            body = functools.partial(
                _run_persistent,
                prog=prog,
                mode=self.mode,
                mesh_shape=self._mesh_shape,
                n_iters=self.n_iters,
                slots=self._slots,
                reduce_fn=self.reduce_fn,
                unroll=self.unroll if self.unroll is not None
                else (2 if (self.double_buffer and self.n_iters > 1) else 1),
                coalesce=self.coalesce,
                sanitize=self.sanitize,
            )
        sharded = jax.shard_map(
            body, mesh=self.mesh, in_specs=(specs,), out_specs=out_specs,
            check_vma=False,
        )
        donate = (0,) if self.donate else ()
        return jax.jit(sharded, donate_argnums=donate)


# -- device-resident loop body (runs inside shard_map, traced once) ----------


def _run_persistent(
    mem: Dict[str, jax.Array],
    *,
    prog: STProgram,
    mode: str,
    mesh_shape: Dict[str, int],
    n_iters: int,
    slots: Tuple[str, ...],
    reduce_fn,
    unroll: int,
    coalesce: bool = True,
    sanitize: bool = False,
):
    mem = dict(mem)
    # two copies of each message slot, rotated zero-copy through the
    # carry: iteration i reads `cur` (the copy written at i-2) and its
    # write becomes the next iteration's `alt` — no stacked arrays, no
    # per-iteration dynamic_update copies.  Both copies start as the
    # same initial value (aliased, never materialized twice).
    cur_slots = {n: mem.pop(n) for n in slots}
    alt_slots = dict(cur_slots)
    tokens, comps = fresh_token_banks(prog)
    # None is an empty pytree node: no dead carry when reductions are off
    red = jnp.zeros((n_iters,), jnp.float32) if reduce_fn is not None else None

    def one_iter(i, carry):
        mem, cur_slots, alt_slots, tokens, comps, red = carry
        cur = dict(mem)
        cur.update(cur_slots)
        cur, tokens, comps = _interpret_program(
            cur, prog=prog, mode=mode, mesh_shape=mesh_shape,
            tokens=tokens, comp_tokens=comps, coalesce=coalesce,
            sanitize=sanitize)
        if reduce_fn is not None:  # sees every buffer, slots included
            with jax.named_scope("residual"):
                val = jnp.asarray(reduce_fn(cur), jnp.float32).reshape(())
                red = jax.lax.dynamic_update_index_in_dim(red, val, i, axis=0)
        written = {n: cur.pop(n) for n in slots}
        return cur, alt_slots, written, tokens, comps, red

    mem, _, last_slots, tokens, comps, red = jax.lax.fori_loop(
        0, n_iters, one_iter,
        (mem, cur_slots, alt_slots, tokens, comps, red),
        unroll=unroll)

    # the rotation leaves the last iteration's writes in the alt carry
    mem.update(last_slots)
    if reduce_fn is not None:
        return mem, red
    return mem


def _run_persistent_while(
    mem: Dict[str, jax.Array],
    *,
    prog: STProgram,
    mode: str,
    mesh_shape: Dict[str, int],
    max_iters: int,
    slots: Tuple[str, ...],
    reduce_fn,
    cond_fn,
    coalesce: bool = True,
    sanitize: bool = False,
):
    """Predicate-terminated variant: ``lax.while_loop`` until
    ``cond_fn(reduction)`` goes False (or ``max_iters`` is hit).

    The carry threads the iteration counter explicitly (a while_loop has
    no induction variable) for the reduction-trace index; the slot
    rotation itself is induction-free, so the last realized write sits
    in the ``alt`` carry position however many iterations run.
    """
    mem = dict(mem)
    # zero-copy rotation, as in _run_persistent
    cur_slots = {n: mem.pop(n) for n in slots}
    alt_slots = dict(cur_slots)
    tokens, comps = fresh_token_banks(prog)
    red = jnp.zeros((max_iters,), jnp.float32)

    def cond(carry):
        i, keep_going, *_ = carry
        return jnp.logical_and(keep_going, i < max_iters)

    def body(carry):
        i, _, mem, cur_slots, alt_slots, tokens, comps, red = carry
        cur = dict(mem)
        cur.update(cur_slots)
        cur, tokens, comps = _interpret_program(
            cur, prog=prog, mode=mode, mesh_shape=mesh_shape,
            tokens=tokens, comp_tokens=comps, coalesce=coalesce,
            sanitize=sanitize)
        with jax.named_scope("residual"):
            val = jnp.asarray(reduce_fn(cur), jnp.float32).reshape(())
            red = jax.lax.dynamic_update_index_in_dim(red, val, i, axis=0)
        written = {n: cur.pop(n) for n in slots}
        keep_going = jnp.asarray(cond_fn(val), jnp.bool_).reshape(())
        return i + 1, keep_going, cur, alt_slots, written, tokens, comps, red

    # the first iteration always runs: there is no reduction to test yet
    carry0 = (jnp.zeros((), jnp.int32), jnp.asarray(True),
              mem, cur_slots, alt_slots, tokens, comps, red)
    n_done, _, mem, _, last_slots, tokens, comps, red = jax.lax.while_loop(
        cond, body, carry0)

    # at least one iteration always ran, so the last realized write is
    # in the alt position — no dynamic parity selection needed
    mem.update(last_slots)
    return mem, red, n_done


def _run_schedule_while(
    mem: Dict[str, jax.Array],
    *,
    sched,
    mode: str,
    mesh_shape: Dict[str, int],
    slots: Tuple[str, ...],
    reduce_fns: Dict[str, Callable],
    coalesce: bool = True,
    sanitize: bool = False,
):
    """Multi-queue variant: every sub-program runs to its OWN iteration
    count / predicate inside one ``while_loop``.

    Each iteration interprets the whole interleaved schedule, then a
    per-program ``active`` flag masks the result: an inactive (already
    terminated) program's buffers, slot copies and reduction trace keep
    their frozen values, so its final state is bit-identical to an
    independent run of that program alone.  Slot double-buffering uses
    the same zero-copy ``(cur, alt)`` rotation as the single-program
    loops, masked per program: an active program's pair rotates, a
    frozen program's pair stays put — so every program's last realized
    write ends (and stays) in the ``alt`` position, and no per-program
    parity bookkeeping is needed.
    """
    subs = sched.subs
    max_iters = max(s.n_iters for s in subs)
    name_of_pid = {s.pid: s.name for s in subs}
    pid_of_buf = {b: s.pid for s in subs for b in s.buffers}

    mem = dict(mem)
    cur_slots = {n: mem.pop(n) for n in slots}
    alt_slots = dict(cur_slots)
    tokens, comps = fresh_token_banks(sched)
    reds = {nm: jnp.zeros((max_iters,), jnp.float32) for nm in reduce_fns}
    active0 = {s.name: jnp.asarray(True) for s in subs}
    ndone0 = {s.name: jnp.zeros((), jnp.int32) for s in subs}

    def act_of(active, buf):
        return active[name_of_pid[pid_of_buf[buf]]]

    def cond(carry):
        i, active, *_ = carry
        any_active = functools.reduce(jnp.logical_or, active.values())
        return jnp.logical_and(any_active, i < max_iters)

    def body(carry):
        i, active, ndone, mem, cur_slots, alt_slots, tokens, comps, reds = carry
        cur = dict(mem)
        cur.update(cur_slots)
        new, tokens, comps = _interpret_program(
            cur, prog=sched, mode=mode, mesh_shape=mesh_shape,
            tokens=tokens, comp_tokens=comps, coalesce=coalesce,
            sanitize=sanitize)

        # per-program reductions, realized counts and continue flags
        ndone = dict(ndone)
        reds = dict(reds)
        keep = {}
        for s in subs:
            act = active[s.name]
            val = None
            if s.name in reduce_fns:
                with jax.named_scope("residual"):
                    val = jnp.asarray(
                        reduce_fns[s.name](new), jnp.float32).reshape(())
                    rec = jax.lax.dynamic_update_index_in_dim(
                        reds[s.name], val, i, axis=0)
                    reds[s.name] = jnp.where(act, rec, reds[s.name])
            done = ndone[s.name] + act.astype(jnp.int32)
            ndone[s.name] = done
            k = jnp.logical_and(act, done < s.n_iters)
            if s.until is not None:
                k = jnp.logical_and(
                    k, jnp.asarray(s.until(val), jnp.bool_).reshape(()))
            keep[s.name] = k

        # masked state update: a terminated program's buffers freeze at
        # its own convergence point (the interpreter still ran them this
        # pass, but the results are discarded).  Slot pairs rotate only
        # while their program is active.
        new_cur, new_alt = {}, {}
        for n in slots:
            act = act_of(active, n)
            written = new.pop(n)
            new_cur[n] = jnp.where(act, alt_slots[n], cur_slots[n])
            new_alt[n] = jnp.where(act, written, alt_slots[n])
        out_mem = {
            n: jnp.where(act_of(active, n), new[n], mem[n]) for n in mem
        }
        return (i + 1, keep, ndone, out_mem, new_cur, new_alt,
                tokens, comps, reds)

    # the first iteration always runs for every program
    carry0 = (jnp.zeros((), jnp.int32), active0, ndone0,
              mem, cur_slots, alt_slots, tokens, comps, reds)
    _, _, ndone, mem, _, alt_slots, tokens, comps, reds = jax.lax.while_loop(
        cond, body, carry0)

    # every program's last realized write froze in the alt position
    mem.update(alt_slots)
    return mem, reds, ndone
