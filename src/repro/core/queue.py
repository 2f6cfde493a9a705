"""STQueue — the ``MPIX_Queue`` analogue and the ST enqueue API.

Maps the paper's proposed interface (Fig. 4/5) onto JAX:

=====================   =====================================================
Paper                   Here
=====================   =====================================================
MPIX_Create_queue       ``STQueue(mesh, ...)`` / ``create_queue(...)``
MPIX_Free_queue         ``queue.free()`` (resource bookkeeping + reuse guard)
MPIX_Enqueue_send       ``queue.enqueue_send(buf, peer, tag)``
MPIX_Enqueue_recv       ``queue.enqueue_recv(buf, peer, tag)``
MPIX_Enqueue_start      ``queue.enqueue_start()``
MPIX_Enqueue_wait       ``queue.enqueue_wait()``
(kernel launch)         ``queue.enqueue_kernel(fn, reads, writes)`` /
                        ``queue.enqueue_compute(fn, reads=, writes=)``
                        (keyword alias — the per-chunk compute hook of
                        the collective-matmul verbs)
(extension)             ``queue.enqueue_collective(op, buf, out, axis)``
(collective matmul,     ``repro.core.collectives.CollectiveQueue``:
 §V-F "how the          ``enqueue_all_gather / enqueue_reduce_scatter /
 schedule is            enqueue_all_to_all`` — ring collectives emitted
 expressed decides      as ordinary trigger→wait channels with per-chunk
 the win")              ``enqueue_compute`` kernels inside the windows;
                        builders ``build_all_gather_matmul`` /
                        ``build_matmul_reduce_scatter`` /
                        ``build_all_to_all`` / ``build_tp_block`` (the
                        "transformer block as ST schedule") return
                        engine-ready programs bit-identical to the
                        decomposed ``core.overlap`` lowerings, so model
                        parallelism inherits coalescing, STLint
                        (ST013/ST014 ring rules), `schedule_cost`
                        pricing, `tune()` and 1-dispatch persistence
(multi-queue)           ``compose(progA, progB, ...)`` /
                        ``prog.concurrent_with(...)`` → :class:`STSchedule`
                        (:mod:`.schedule` — N queues, one device program)
(cross-queue            ``enqueue_send/recv(..., remote="peerprog")`` +
 channels)              ``compose(..., links=[("A","B"), ...])``: a send in
                        queue A deposits into queue B's memory, trigger on
                        A's counter bank, completion on B's — B's wait gate
                        observes A's completion (halo exchange *between*
                        composed queues)
(§V-A contiguous        ``build(coalesce=True)`` →
 MPI buffer)            :class:`~repro.core.matching.CoalescedChannel` plan
                        per batch: matched channels grouped by
                        ``(axis, permutation)`` and lowered to ONE fused
                        by-axis transfer each (26 → ≤6 collectives per
                        start gate for direct26), bit-identical deposits
(DWQ validation        ``build(verify="warn"|"error"|"off")`` /
 face)                  ``compose(..., verify=)`` → :mod:`repro.core.verify`:
                        a static pass symbolically executes the program's
                        trigger/completion counter banks in stream order
                        and emits ``ST0xx`` :class:`~repro.core.verify.
                        Diagnostic` records (deadlocked waits, slot races,
                        counter drift, structural lint) with enqueue-site
                        provenance — the build-time stand-in for the
                        debugger the NIC's offloaded DWQ does not have;
                        ``engine(..., sanitize=True)`` adds the runtime
                        NaN-canary sanitizer
(effect/race            ``repro.core.effects`` + the happens-before
 analysis face)         analysis of ``repro.core.verify``: every batch
                        records its declared effect set
                        (``Batch.effects`` — pack reads, staging
                        traffic, deposits; kernels carry ``reads=``/
                        ``writes=``), rules ST015–ST018 prove a program
                        race-free under EVERY interleave policy (not
                        just the emitted order), and
                        ``effects.certify_equivalence`` proves a
                        transformed candidate's per-buffer effect trace
                        equal to its baseline's — consumed by ``tune()``
                        (certified candidates skip allclose) and
                        ``python -m repro.analysis --strict``
(§V-C hand-tuned        ``repro.launch.tune.tune``: a generic knob search
 shaders)               (trigger mode, coalescing, interleave policy,
                        double-buffer/unroll) over a built program —
                        candidates priced by the analytic cost model
                        (``repro.launch.costing.schedule_cost``),
                        STLint-verified, the cheapest few measured; the
                        software analogue of tuning the NIC's trigger
                        shaders by hand, made self-optimizing
(ML serving face)       ``repro.launch.serve.ServeEngine``: greedy decode
                        as a device-resident masked while_loop (ONE host
                        dispatch per chunk, per-sequence EOS/max-len
                        termination — the per-program ``n_done`` idiom at
                        per-sequence grain), continuous-batching admission
                        as a composed prefill+decode dispatch, cache slots
                        recycled via donation (zero-copy rotation)
=====================   =====================================================

All enqueue operations are **non-blocking descriptor appends** — nothing
touches a device.  ``build()`` performs trace-time matching and returns
an immutable :class:`STProgram`; the two engines
(:mod:`.engine_fused`, :mod:`.engine_host`) execute it.

Semantics preserved from the paper:

* FIFO execution of enqueued operations per queue;
* batching: one ``start`` triggers every comm op enqueued since the
  previous ``start`` (one writeValue per batch, not per op);
* ``wait`` blocks only the *stream* (in the fused engine, a data-
  dependency gate; the host never blocks), and host-level ``MPI_Wait``
  style blocking exists separately (``engine_host`` sync points);
* no wildcards — matching is static (see :mod:`.matching`);
* a queue may be reused across iterations (the program re-executes).
  ``STProgram.persistent(n_iters)`` promotes that reuse to a device-
  resident loop (one host dispatch for all iterations — see
  :mod:`.engine_persistent`); it requires the queue to be *quiescent*
  per pass (every started batch waited), which ``persistent`` enforces;
* several *independent* queues may be in flight concurrently: build one
  program per queue and fuse them with :func:`repro.core.schedule.compose`
  (or ``progA.concurrent_with(progB)``).  The composed
  :class:`~repro.core.schedule.STSchedule` interleaves the programs'
  batches round-robin with namespaced buffers and per-program counter
  banks, so one queue's communication overlaps another's compute in a
  single host dispatch — the multi-DWQ pipelined schedule;
* concurrent queues may also *chain*: a send/recv enqueued with
  ``remote=<peer program>`` stays open through this queue's build and
  is matched by ``compose`` into a cross-program channel — triggered by
  the sender's counters, deposited into the receiver's memory,
  completed on the receiver's counters (so the receiver's ``wait``
  observes it).  This is the halo exchange *between* composed queues
  (e.g. :func:`repro.core.halo.build_faces_part_program`).
"""

from __future__ import annotations

import dataclasses
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from .counters import CompletionCounter, TriggerCounter
from .descriptors import (
    BufferSpec,
    CollDesc,
    KernelDesc,
    RecvDesc,
    SendDesc,
    StartDesc,
    WaitDesc,
)
from .effects import batch_effects, stamp_staging
from .matching import (
    Batch,
    MatchError,
    coalesce_batch,
    match_batch,
    validate_program_order,
)


def _adapt_arity(fn: Callable, n_reads: int) -> Callable:
    """Adapt a kernel to the conservative implicit-reads fallback.

    The engines call ``fn(*reads)`` positionally; when the queue widens
    an undeclared read set to every buffer, a kernel written for fewer
    arguments would crash at trace time — so pass it only the prefix it
    was written for.  Kernels taking ``*args`` are left untouched (they
    accept the widened call by construction).
    """
    import inspect

    try:
        params = inspect.signature(fn).parameters.values()
    except (TypeError, ValueError):
        return fn
    if any(p.kind == inspect.Parameter.VAR_POSITIONAL for p in params):
        return fn
    arity = sum(p.kind in (inspect.Parameter.POSITIONAL_ONLY,
                           inspect.Parameter.POSITIONAL_OR_KEYWORD)
                for p in params)
    if arity >= n_reads:
        return fn

    def adapted(*vals):
        return fn(*vals[:arity])

    return adapted


def _call_site() -> Optional[str]:
    """``file:line`` of the enqueue call that created a descriptor.

    Walks the extracted stack outward past this module's own frames so
    builder helpers (``halo.py``, user code, tests) are named rather
    than ``queue.py`` itself.  Paths are shortened to their last two
    components — enough to be clickable, short enough for a table.
    """
    for frame in reversed(traceback.extract_stack(limit=8)):
        if frame.filename == __file__:
            continue
        parts = frame.filename.replace("\\", "/").rsplit("/", 2)
        short = "/".join(parts[-2:]) if len(parts) > 1 else parts[-1]
        return f"{short}:{frame.lineno}"
    return None


@dataclasses.dataclass
class STProgram:
    """Immutable, matched ST program ready for an engine."""

    buffers: Dict[str, BufferSpec]
    descriptors: Tuple[Any, ...]
    batches: Tuple[Batch, ...]
    mesh: Any  # jax.sharding.Mesh
    name: str = "st_program"
    # Persistent-iteration metadata (MPIX_Queue reuse): how many times a
    # single host dispatch re-executes the whole program on-device.  Set
    # via :meth:`persistent`; engines other than PersistentEngine ignore
    # it (they run one pass per dispatch).
    n_iters: int = 1
    # Optional device-resident termination predicate: ``until(reduction)
    # -> bool`` evaluated on the per-iteration scalar reduction inside
    # the loop; the loop keeps running while it returns True (bounded by
    # ``n_iters``, which becomes the max_iters safety bound).  Set via
    # ``persistent(n_iters, until=...)``.
    until: Optional[Callable[[Any], Any]] = None
    # Build-time choices recorded as (name, value) pairs, which engines
    # report as arguments of their host dispatch span (Faces records
    # which lowering its interior stencil took).
    span_args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def n_batches(self) -> int:
        return len(self.batches)

    @property
    def n_channels(self) -> int:
        return sum(len(b.channels) for b in self.batches)

    @property
    def is_coalesced(self) -> bool:
        """True when at least one batch carries a coalescing plan."""
        return any(b.plan is not None for b in self.batches)

    def collective_counts(self) -> Dict[int, Tuple[int, int]]:
        """Per start gate: (uncoalesced, as-lowered) collective counts.

        The uncoalesced count is one collective per matched channel plus
        one per deferred collective; the as-lowered count replaces the
        per-channel collectives with the batch's fused transfers when a
        coalescing plan is recorded (the paper's 26 → ≤6 reduction,
        measurable rather than asserted).
        """
        out: Dict[int, Tuple[int, int]] = {}
        for b in self.batches:
            un = len(b.channels) + len(b.colls)
            co = (len(b.plan.transfers) if b.plan is not None
                  else len(b.channels)) + len(b.colls)
            out[b.index] = (un, co)
        return out

    def max_collectives_per_start(self) -> Tuple[int, int]:
        """Max over start gates of (uncoalesced, as-lowered) counts."""
        counts = self.collective_counts()
        if not counts:
            return (0, 0)
        return (max(u for u, _ in counts.values()),
                max(c for _, c in counts.values()))

    @property
    def is_persistent(self) -> bool:
        return self.n_iters > 1 or self.until is not None

    @property
    def open_links(self) -> int:
        """Number of unresolved cross-program (``remote=``) descriptors.

        Nonzero means this program declares channels whose peer lives in
        another program: it must go through
        :func:`repro.core.schedule.compose` (which resolves them into
        cross-program channels) before any engine may run it.
        """
        return sum(len(b.open_sends) + len(b.open_recvs)
                   for b in self.batches)

    def require_closed(self) -> None:
        """Raise unless every cross-program descriptor is resolved
        (engines call this: an open channel has no matching side and
        would hang)."""
        if self.open_links:
            raise ValueError(
                f"[ST012] program {self.name!r} has {self.open_links} unresolved "
                f"cross-program (remote=) descriptor(s): compose() it with "
                f"its peer program(s) before running — an open channel has "
                f"no matching side and would hang")

    def buffers_by_pid(self) -> Dict[int, Tuple[str, ...]]:
        """Buffer names grouped by owning program id.

        A plain single-queue program owns every buffer under pid 0; a
        composed :class:`~repro.core.schedule.STSchedule` overrides this
        with one entry per sub-program, which is what lets the engines
        keep stream-FIFO ordering (and counter banks) *per program*
        instead of serializing the whole composition.
        """
        return {0: tuple(self.buffers)}

    def concurrent_with(self, *others: "STProgram",
                        name: Optional[str] = None) -> "STProgram":
        """Fuse this program with ``others`` into one
        :class:`~repro.core.schedule.STSchedule` — sugar for
        ``compose(self, *others)`` (see :mod:`repro.core.schedule`)."""
        from .schedule import compose  # local import: schedule imports us
        return compose(self, *others, name=name)

    def persistent(self, n_iters: int,
                   until: Optional[Callable[[Any], Any]] = None) -> "STProgram":
        """Mark the program for device-resident re-execution.

        Returns a copy whose ``n_iters`` requests that an engine keep the
        *entire* iteration loop on-device — the MPIX_Queue-reuse contract
        ("a queue may be reused across iterations") delivered without a
        host round-trip per iteration.

        With ``until`` set the iteration count becomes *dynamic*: the
        engine re-runs the program while ``until(reduction)`` stays True
        (e.g. ``lambda residual: residual >= tol``), with ``n_iters`` as
        the max-iteration safety bound.  The predicate runs inside the
        device loop, on the per-iteration scalar reduction — zero host
        syncs until converged.

        Reuse guards: re-execution is only well-defined when the queue is
        *quiescent* at the end of a pass — a ``wait`` must follow the
        final ``start`` so every triggered completion is observed before
        the next pass begins (the completion counter is cumulative, so
        one trailing wait covers all earlier batches; without it,
        iteration i+1's triggers could fire against iteration i's
        in-flight completions).  A predicate-terminated loop may always
        run more than one pass, so ``until`` triggers the guard even
        when the bound is 1.
        """
        if n_iters < 1:
            raise QueueError(f"persistent n_iters must be >= 1, got {n_iters}")
        last_start = last_wait = -1
        for i, d in enumerate(self.descriptors):
            if isinstance(d, StartDesc):
                last_start = i
            elif isinstance(d, WaitDesc):
                last_wait = i
        if ((n_iters > 1 or until is not None)
                and last_start >= 0 and last_wait < last_start):
            raise QueueError(
                "persistent reuse of a non-quiescent queue: the final "
                "enqueue_start has no following enqueue_wait; counters "
                "would not agree across iterations"
            )
        return dataclasses.replace(self, n_iters=n_iters, until=until)

    def dispatch_count_host(self) -> int:
        """How many separate device dispatches the host-orchestrated
        (baseline / progress-thread) engine needs — the paper's
        'expensive synchronization points'."""
        n = 0
        for d in self.descriptors:
            if isinstance(d, KernelDesc):
                n += 1
        for b in self.batches:
            n += len(b.channels) + len(b.colls)
        return n

    def dispatch_count_fused(self) -> int:
        """The fused ST engine dispatches the whole program once (so a
        Faces loop of N iterations costs N host dispatches)."""
        return 1

    def dispatch_count_persistent(self) -> int:
        """The persistent engine dispatches once for ALL ``n_iters``
        iterations — the device owns the loop, the host dispatches 1."""
        return 1


class QueueError(RuntimeError):
    pass


class STQueue:
    """Records an ST program (the MPIX_Queue + GPU-stream pair).

    Parameters
    ----------
    mesh:
        The ``jax.sharding.Mesh`` the program communicates over.  Plays
        the role of the MPI communicator.
    name:
        Diagnostic name (shows up in lowered HLO metadata).
    """

    def __init__(self, mesh, name: str = "stq"):
        self.mesh = mesh
        self.name = name
        self._descs: List[Any] = []
        self._buffers: Dict[str, BufferSpec] = {}
        self._trigger = TriggerCounter(name=f"{name}.trigger")
        self._completion = CompletionCounter(name=f"{name}.completion")
        self._freed = False
        self._built: Optional[STProgram] = None
        self._built_key: Optional[Tuple[str, bool]] = None

    # -- buffer declaration -------------------------------------------------

    def buffer(self, name: str, shape: Sequence[int], dtype=np.float32, pspec: Sequence[Any] = ()) -> str:
        """Declare a named global buffer the program operates on."""
        self._check_live()
        if name in self._buffers:
            raise QueueError(f"buffer {name!r} already declared")
        self._buffers[name] = BufferSpec(name, tuple(shape), dtype, tuple(pspec))
        self._built = None
        return name

    # -- enqueue API (paper Fig. 5) ------------------------------------------

    def enqueue_kernel(
        self, fn: Callable, reads: Sequence[str], writes: Sequence[str], name: str = "kernel"
    ) -> None:
        """Enqueue a compute kernel on the stream (non-blocking)."""
        self._check_live()
        for b in tuple(reads) + tuple(writes):
            if b not in self._buffers:
                raise QueueError(f"kernel touches undeclared buffer {b!r}")
        self._descs.append(
            KernelDesc(fn, tuple(reads), tuple(writes), name,
                       site=_call_site()))
        self._built = None

    def enqueue_compute(self, fn: Callable, *,
                        reads: Optional[Sequence[str]] = None,
                        writes: Sequence[str] = (),
                        name: str = "compute") -> None:
        """Keyword alias of :meth:`enqueue_kernel` — the per-chunk
        compute hook used by the collective-matmul verbs
        (:mod:`repro.core.collectives`): a kernel enqueued between a
        ring step's start and the next step's trigger runs inside that
        trigger→wait window, which is where overlap comes from.

        Omitting ``reads=`` does NOT make the kernel effect-free: the
        queue substitutes the conservative fallback — the kernel is
        assumed to read **every** buffer declared so far (its declared
        writes stay as given) — and the descriptor is flagged
        ``implicit_effects``, which STLint reports as the ST019 warning.
        Implicit effects over-serialize the happens-before analysis
        (every pending deposit looks like a race with this kernel), so
        declare ``reads=``/``writes=`` explicitly; the in-repo builders
        are lint-enforced to (``scripts/lint_repo.py``).
        """
        implicit = reads is None
        if implicit:
            all_bufs = tuple(self._buffers)
            fn = _adapt_arity(fn, len(all_bufs))
            reads = all_bufs
        self.enqueue_kernel(fn, reads, writes, name=name)
        if implicit:
            self._descs[-1] = dataclasses.replace(
                self._descs[-1], implicit_effects=True)

    def enqueue_send(self, buf: str, peer, tag: int, region=None,
                     remote: Optional[str] = None) -> None:
        """MPIX_Enqueue_send: deferred tagged send (returns immediately).

        With ``remote=<program name>`` the matching receive lives in
        another queue's program: the send stays *open* through this
        queue's build and is matched by
        :func:`repro.core.schedule.compose` into a cross-program
        channel depositing into the peer program's memory.
        """
        self._check_live()
        self._check_buf(buf)
        self._descs.append(
            SendDesc(buf, peer, tag, threshold=self._trigger.next_threshold(),
                     region=region, remote=remote, site=_call_site())
        )
        self._built = None

    def enqueue_recv(self, buf: str, peer, tag: int, region=None, mode: str = "replace",
                     remote: Optional[str] = None) -> None:
        """MPIX_Enqueue_recv: deferred tagged receive (returns immediately).

        With ``remote=<program name>`` the matching send lives in
        another queue's program (see :meth:`enqueue_send`); the wait
        covering this batch then gates on the *sender's* completion,
        wired across the per-program counter banks by the engines.
        """
        self._check_live()
        self._check_buf(buf)
        if mode not in ("replace", "add"):
            raise QueueError("recv mode must be 'replace' or 'add'")
        self._descs.append(
            RecvDesc(buf, peer, tag, threshold=self._trigger.next_threshold(),
                     region=region, mode=mode, remote=remote,
                     site=_call_site())
        )
        self._built = None

    def enqueue_collective(self, op: str, buf: str, out: str, axis, **kwargs) -> None:
        """Beyond-paper: enqueue a whole collective as one deferred op."""
        self._check_live()
        self._check_buf(buf)
        if out not in self._buffers:
            raise QueueError(f"collective writes undeclared buffer {out!r}")
        if op not in ("all_gather", "reduce_scatter", "all_reduce", "all_to_all", "ppermute"):
            raise QueueError(f"unknown collective {op!r}")
        self._descs.append(
            CollDesc(op, buf, out, axis, kwargs,
                     threshold=self._trigger.next_threshold(),
                     site=_call_site())
        )
        self._built = None

    def enqueue_start(self) -> None:
        """MPIX_Enqueue_start: one trigger (writeValue) for the batch of
        every comm op enqueued since the previous start."""
        self._check_live()
        batch = self._trigger.record_start()
        self._descs.append(
            StartDesc(batch=batch - 1, threshold=batch, site=_call_site()))
        self._built = None

    def enqueue_wait(self) -> None:
        """MPIX_Enqueue_wait: stream-blocking completion gate (waitValue).
        Non-blocking for the host."""
        self._check_live()
        n_started = self._trigger.scheduled
        if n_started == 0:
            raise QueueError("enqueue_wait before any enqueue_start")
        self._descs.append(
            WaitDesc(batch=n_started - 1,
                     expected=self._completion.record_op(),
                     site=_call_site()))
        self._built = None

    def free(self) -> None:
        """MPIX_Free_queue: releases the queue.  Caller is responsible for
        having completed outstanding work (paper §III-A).

        Also drops the built-program cache: a program built, freed, then
        rebuilt under a reused queue name must never be served
        descriptors that reference the freed queue's resources.
        """
        self._check_live()
        self._freed = True
        self._built = None

    # -- build ---------------------------------------------------------------

    def build(self, name: Optional[str] = None,
              coalesce: bool = True, verify: str = "warn") -> STProgram:
        """Trace-time matching + validation → immutable STProgram.

        With ``coalesce=True`` (default) every batch's matched channels
        are additionally grouped into fused by-axis transfers
        (:func:`~repro.core.matching.coalesce_batch`, the paper's §V-A
        contiguous-buffer step) and the plan is recorded on the batch;
        engines execute the plan when present and results stay
        bit-identical to the uncoalesced lowering.

        ``verify`` runs the :mod:`repro.core.verify` static pass on the
        built program: ``"warn"`` (default) reports every diagnostic as
        an :class:`~repro.core.verify.STLintWarning`, ``"error"`` raises
        :class:`~repro.core.verify.VerifyError` on error-severity
        diagnostics (warnings still warn), ``"off"`` skips the pass.
        A program with open ``remote=`` descriptors is only checked for
        single-queue rules here; :func:`repro.core.schedule.compose`
        re-verifies the whole schedule (default ``"error"``) once the
        cross-program links are resolved.
        """
        self._check_live()
        resolved = name or self.name
        # the cache is keyed on the resolved program name AND the
        # coalesce flag: a second build("other") — or a rebuild with
        # coalescing toggled — must not hand back the cached program.
        # (verify is not part of the key: it never changes the program,
        # so the pass simply re-runs on the cached result.)
        if self._built is not None and self._built_key == (resolved, coalesce):
            from .verify import run_verify  # local: verify imports queue
            run_verify(self._built, verify)
            return self._built
        validate_program_order(self._descs)
        mesh_shape = dict(self.mesh.shape)

        batches: List[Batch] = []
        pending_sends: List[SendDesc] = []
        pending_recvs: List[RecvDesc] = []
        pending_colls: List[CollDesc] = []
        kernels_since_start: List[KernelDesc] = []
        for d in self._descs:
            if isinstance(d, KernelDesc):
                kernels_since_start.append(d)
            elif isinstance(d, SendDesc):
                pending_sends.append(d)
            elif isinstance(d, RecvDesc):
                pending_recvs.append(d)
            elif isinstance(d, CollDesc):
                pending_colls.append(d)
            elif isinstance(d, StartDesc):
                # remote= sends/recvs pair with another program: leave
                # them open for compose() instead of matching here
                local_sends = [s for s in pending_sends if s.remote is None]
                local_recvs = [r for r in pending_recvs if r.remote is None]
                open_sends = [s for s in pending_sends if s.remote is not None]
                open_recvs = [r for r in pending_recvs if r.remote is not None]
                for o in open_sends + open_recvs:
                    if o.remote == resolved:
                        raise QueueError(
                            f"remote={resolved!r} names this program itself: "
                            f"a channel to the own queue is a plain (local) "
                            f"send/recv pair, not a cross-program link")
                channels = match_batch(local_sends, local_recvs)
                plan = stamp_staging(
                    coalesce_batch(channels, self._buffers, mesh_shape)
                    if coalesce else None, d.batch)
                batch = Batch(
                    index=d.batch,
                    kernels_before=list(kernels_since_start),
                    channels=channels,
                    colls=list(pending_colls),
                    plan=plan,
                    coalesce=coalesce,
                    open_sends=open_sends,
                    open_recvs=open_recvs,
                )
                batch.effects = batch_effects(batch)
                batches.append(batch)
                pending_sends, pending_recvs, pending_colls = [], [], []
                kernels_since_start = []
            elif isinstance(d, WaitDesc):
                # completion counters are cumulative (see
                # STProgram.persistent): a wait on batch k observes the
                # completions of every batch <= k, so all of them are
                # quiescent after it — not just batch k itself.
                for b in batches[: d.batch + 1]:
                    b.waited = True

        self._built = STProgram(
            buffers=dict(self._buffers),
            descriptors=tuple(self._descs),
            batches=tuple(batches),
            mesh=self.mesh,
            name=resolved,
        )
        self._built_key = (resolved, coalesce)
        from .verify import run_verify  # local import: verify imports queue
        run_verify(self._built, verify)
        return self._built

    # -- helpers ---------------------------------------------------------------

    def _check_live(self):
        if self._freed:
            raise QueueError("operation on freed MPIX_Queue (use-after-free)")

    def _check_buf(self, buf: str):
        if buf not in self._buffers:
            raise QueueError(f"undeclared buffer {buf!r}")

    @property
    def n_descriptors(self) -> int:
        return len(self._descs)


def create_queue(mesh, name: str = "stq") -> STQueue:
    """MPIX_Create_queue analogue (local operation, no communication)."""
    return STQueue(mesh, name)
