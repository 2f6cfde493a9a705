"""Deferred-execution descriptors — the ST command-queue entries.

An ``STQueue`` records a *program*: an ordered list of descriptors, the
JAX analogue of (a) the NIC command queue holding DWQ entries and (b)
the GPU stream holding kernels and stream-memory ops.  Nothing executes
at enqueue time; an engine executes the program later (fused into one
XLA computation, or host-orchestrated per descriptor).

Descriptor kinds
----------------
``KernelDesc``    a compute kernel enqueued on the stream (D1, D2 in the
                  paper's Fig. 6).  Operates on named buffers.
``SendDesc``      MPIX_Enqueue_send: deferred tagged send to a peer.
``RecvDesc``      MPIX_Enqueue_recv: deferred tagged receive.
``CollDesc``      extension beyond the paper's P2P surface: a whole
                  collective (all-gather / reduce-scatter / all-to-all /
                  all-reduce) as a single deferred descriptor, so model
                  code can route *all* its communication through a queue.
``StartDesc``     MPIX_Enqueue_start: trigger everything enqueued since
                  the previous start (one writeValue for the batch).
``WaitDesc``      MPIX_Enqueue_wait: stream-blocking completion wait
                  (one waitValue for the batch).

Peers
-----
The paper addresses peers by MPI rank.  Under SPMD the same program runs
on every device, so a peer is expressed relationally:

* ``OffsetPeer(axis, delta)`` — "the rank `delta` steps along mesh axis
  `axis`"; non-periodic offsets drop at the boundary (ppermute semantics:
  unmatched receivers get zeros — which is exactly what a halo sum
  wants).
* ``GridOffsetPeer(axes, deltas, periodic)`` — diagonal neighbor on a
  multi-axis grid (the 26-neighbor Faces pattern).
* ``PairListPeer(axis, pairs)`` — explicit (src, dst) rank pairs, the
  closest analogue of the paper's Fig. 7 two-rank example.  Legal
  because ST forbids wildcards: the global pattern is static.

Program identity
----------------
Every descriptor carries a ``pid`` (program id, default 0).  A program
built from a single :class:`~repro.core.queue.STQueue` uses pid 0
throughout; :func:`repro.core.schedule.compose` assigns each fused
sub-program its own pid so the engines can keep **per-program
trigger/completion counter banks** — the multi-DWQ analogue of one
counter pair per ``MPIX_Queue``.

Enqueue-site provenance (``site``)
----------------------------------
Every descriptor records the ``file:line`` of the ``enqueue_*`` call
that created it (captured by :class:`~repro.core.queue.STQueue` via
``traceback.extract_stack``).  Build/compose/verify errors and
:class:`~repro.core.verify.Diagnostic` records carry it, so a failure
in a composed 400-descriptor schedule names the enqueue call at fault
instead of a bare descriptor index.

Cross-program channels (``remote``)
-----------------------------------
``SendDesc``/``RecvDesc`` additionally carry an optional ``remote``
field naming the *peer program* the descriptor pairs with.  A remote
send's matching receive lives in another queue's program (and vice
versa): the queue's own build leaves such descriptors *open*, and
:func:`repro.core.schedule.compose` matches them across the composed
programs into channels whose deposit lands in the peer program's
memory — with the trigger taken from the sender's counter bank and the
completion wired into the *receiver's* bank, so the receiver's wait
gate observes the sender's completion.  This is how concurrent queues
chain triggered operations across streams (the halo exchange *between*
composed domain parts) instead of merely interleaving independently.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np

# --------------------------------------------------------------------------
# Peer specifications
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OffsetPeer:
    axis: str
    delta: int
    periodic: bool = False

    def inverse(self) -> "OffsetPeer":
        return OffsetPeer(self.axis, -self.delta, self.periodic)


@dataclasses.dataclass(frozen=True)
class GridOffsetPeer:
    axes: Tuple[str, ...]
    deltas: Tuple[int, ...]
    periodic: bool = False

    def __post_init__(self):
        if len(self.axes) != len(self.deltas):
            raise ValueError("axes and deltas must align")

    def inverse(self) -> "GridOffsetPeer":
        return GridOffsetPeer(self.axes, tuple(-d for d in self.deltas), self.periodic)


@dataclasses.dataclass(frozen=True)
class PairListPeer:
    axis: str
    pairs: Tuple[Tuple[int, int], ...]  # (src_rank, dst_rank)

    def inverse(self) -> "PairListPeer":
        # From the receiver's point of view the pairs are identical; the
        # match check compares (src, dst) sets directly.
        return PairListPeer(self.axis, self.pairs)


Peer = Any  # OffsetPeer | GridOffsetPeer | PairListPeer


def perm_for(peer: Peer, mesh_shape: dict) -> Tuple[str, Sequence[Tuple[int, int]]]:
    """Resolve a peer spec into (axis_name(s), ppermute permutation).

    For grid offsets the permutation is computed over the *flattened*
    multi-axis grid; the engine ppermutes over the axis tuple.
    Returns (axis or tuple-of-axes, [(src, dst), ...]).
    """
    if isinstance(peer, PairListPeer):
        return peer.axis, list(peer.pairs)

    if isinstance(peer, OffsetPeer):
        n = mesh_shape[peer.axis]
        pairs = []
        for src in range(n):
            dst = src + peer.delta
            if peer.periodic:
                dst %= n
            elif not (0 <= dst < n):
                continue
            pairs.append((src, dst))
        return peer.axis, pairs

    if isinstance(peer, GridOffsetPeer):
        dims = [mesh_shape[a] for a in peer.axes]
        pairs = []
        for src_multi in np.ndindex(*dims):
            dst_multi = []
            ok = True
            for c, d, n in zip(src_multi, peer.deltas, dims):
                t = c + d
                if peer.periodic:
                    t %= n
                elif not (0 <= t < n):
                    ok = False
                    break
                dst_multi.append(t)
            if not ok:
                continue
            src = int(np.ravel_multi_index(src_multi, dims))
            dst = int(np.ravel_multi_index(tuple(dst_multi), dims))
            pairs.append((src, dst))
        return tuple(peer.axes), pairs

    raise TypeError(f"unknown peer spec: {peer!r}")


def hop_decomposition(peer: Peer, axis_order: Sequence[str]):
    """Decompose a peer spec into an ordered list of single-axis hops.

    A grid offset ``(dx, dy, dz)`` is the composition of one shift per
    nonzero component; routing a message through those shifts one mesh
    axis at a time delivers bit-identical payloads to the direct
    multi-axis ``ppermute`` (data is relayed verbatim, and on a
    non-periodic grid every intermediate rank of an axis-ordered path
    exists iff the direct source rank exists).  This is what lets the
    coalescing layer (:mod:`.matching`) share ONE by-axis transfer
    between every channel that hops the same ``(axis, delta)``.

    Hops are emitted in ``axis_order`` (the mesh's axis order) so all
    channels agree on stage numbering.  Returns ``[(axis, delta,
    periodic), ...]`` or ``None`` for peers with no offset structure
    (``PairListPeer`` — coalescable only with channels sharing its
    exact permutation).
    """
    if isinstance(peer, OffsetPeer):
        return [(peer.axis, peer.delta, peer.periodic)]
    if isinstance(peer, GridOffsetPeer):
        order = {a: i for i, a in enumerate(axis_order)}
        if any(a not in order for a in peer.axes):
            return None
        hops = sorted(
            ((a, d, peer.periodic) for a, d in zip(peer.axes, peer.deltas)
             if d != 0),
            key=lambda h: order[h[0]],
        )
        # degenerate all-zero offset: a self-send, one identity hop
        return hops or [(peer.axes[0], 0, peer.periodic)]
    return None


# --------------------------------------------------------------------------
# Descriptors
# --------------------------------------------------------------------------


@dataclasses.dataclass
class KernelDesc:
    """A compute kernel enqueued on the stream.

    ``fn(*reads) -> writes`` must be a pure JAX function over the *local*
    (per-shard) views of the named buffers.  ``writes`` names receive the
    outputs positionally.
    """

    fn: Callable
    reads: Tuple[str, ...]
    writes: Tuple[str, ...]
    name: str = "kernel"
    # Program identity (multi-queue composition; see module docstring).
    pid: int = 0
    # Enqueue-site provenance ("file:line"; see module docstring).
    site: Optional[str] = None
    # True when the effect set was NOT declared by the caller and the
    # queue substituted the conservative reads-everything fallback
    # (enqueue_compute with no reads=/writes=).  Surfaced as the ST019
    # warning: implicit effects over-serialize the happens-before graph
    # and weaken every race rule built on it.
    implicit_effects: bool = False


@dataclasses.dataclass
class SendDesc:
    buf: str
    peer: Peer
    tag: int
    # Trigger threshold (SS11 DWQ field); filled in by the queue.
    threshold: int = -1
    # Optional slice of the buffer to send: tuple of slice objects.
    region: Optional[Tuple[slice, ...]] = None
    pid: int = 0
    # Cross-program channel: name of the peer *program* holding the
    # matching receive (None = matched within this program's own batch).
    remote: Optional[str] = None
    # Enqueue-site provenance ("file:line"; see module docstring).
    site: Optional[str] = None


@dataclasses.dataclass
class RecvDesc:
    buf: str
    peer: Peer
    tag: int
    threshold: int = -1
    region: Optional[Tuple[slice, ...]] = None
    # How to deposit into the destination buffer: "replace" or "add"
    # ("add" is the Faces gather-scatter sum deposit).
    mode: str = "replace"
    pid: int = 0
    # Cross-program channel: name of the peer *program* holding the
    # matching send (None = matched within this program's own batch).
    remote: Optional[str] = None
    # Enqueue-site provenance ("file:line"; see module docstring).
    site: Optional[str] = None


@dataclasses.dataclass
class CollDesc:
    """A deferred collective (beyond-paper extension)."""

    op: str  # all_gather | reduce_scatter | all_reduce | all_to_all | ppermute
    buf: str
    out: str
    axis: Any  # mesh axis name or tuple
    kwargs: dict = dataclasses.field(default_factory=dict)
    threshold: int = -1
    pid: int = 0
    # Enqueue-site provenance ("file:line"; see module docstring).
    site: Optional[str] = None


@dataclasses.dataclass
class StartDesc:
    batch: int  # index of the batch this start triggers
    threshold: int = -1
    pid: int = 0
    # Enqueue-site provenance ("file:line"; see module docstring).
    site: Optional[str] = None


@dataclasses.dataclass
class WaitDesc:
    batch: int
    expected: int = -1  # completion-counter target
    pid: int = 0
    # Enqueue-site provenance ("file:line"; see module docstring).
    site: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class BufferSpec:
    """Global-view buffer declaration for a queue program."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any
    # PartitionSpec entries (axis names / None) for the global array.
    pspec: Tuple[Any, ...] = ()


Descriptor = Any  # union of the above
