"""Faces — the paper's microbenchmark pattern as an ST program.

Faces (paper §V-A) is the nearest-neighbor pattern of CORAL-2 Nekbone:
each rank owns a 3-D block of spectral-element data and exchanges the
**faces (6), edges (12) and corners (8)** of its block with up to 26
neighbors, then *adds* the received contributions into its own boundary
(direct-stiffness summation).  The timed inner loop is:

1. pre-post receives;            (enqueue_recv ×26)
2. pack boundary slabs;          (pack kernels — Pallas or jnp)
3. initiate sends;               (enqueue_send ×26 + one enqueue_start)
4. interior compute (overlap);   (enqueue_kernel)
5. wait for messages;            (enqueue_wait)
6. unpack-and-add.               (unpack kernels)

This module builds that inner loop as an :class:`STQueue` program over a
3-D device grid, with the paper's variants selectable:

* ``engine``: ``fused`` (ST — one dispatch) vs ``host`` (baseline —
  per-op dispatch + host sync; Fig. 1);
* ``granularity``: ``direct26`` (paper: one message per neighbor) or
  ``staged3`` (beyond-paper: three axis sweeps, 6 larger messages, with
  corner/edge data forwarded through already-updated ghosts);
* ``batched``: one ``start`` for all messages (paper's batching) or one
  ``start`` per message (models unbatched triggering);
* ``pack``: ``jnp`` slicing or the Pallas ``halo_pack`` kernel.

For the *timed loop around* the inner exchange there are three control
paths: per-op host dispatch (:mod:`.engine_host`), one dispatch per
iteration (:mod:`.engine_fused`), and — via
:func:`run_faces_persistent` / :mod:`.engine_persistent` — one dispatch
for the whole N-iteration loop, device-resident.  On top of that,
:func:`run_faces_pipelined` splits the domain into N x-parts (uneven
sizes OK) on the same mesh, gives each its own queue, and composes the
persistent loops (:mod:`.schedule`) so they interleave in ONE dispatch.
By default the parts are *linked* through cross-program channels
(:func:`build_faces_part_program`): every iteration they exchange their
shared interior faces and the stencil's ghost planes, so the composed
run is the TRUE full-domain solve — bit-identical to the single-queue
:func:`run_faces_persistent` in ``stream`` mode (and in uncoalesced
``dataflow``; the default dataflow+coalesce path agrees to a few
documented FMA-contraction ULPs — see tests/test_links.py) — while one
part's communication window still overlaps another's compute.  With
``exchange=False`` the parts iterate independently (each may terminate
on its own convergence predicate).

A pure-NumPy oracle (`faces_oracle`) computes the same update globally
for correctness tests.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .descriptors import GridOffsetPeer
from .queue import STQueue, STProgram

AXES3 = ("gx", "gy", "gz")

# all 26 neighbor direction vectors, deterministic order: faces first,
# then edges, then corners (paper packs/sends in this order).
DIRECTIONS: Tuple[Tuple[int, int, int], ...] = tuple(
    sorted(
        (d for d in itertools.product((-1, 0, 1), repeat=3) if any(d)),
        key=lambda d: (sum(map(abs, d)), d),
    )
)
FACES = tuple(d for d in DIRECTIONS if sum(map(abs, d)) == 1)
EDGES = tuple(d for d in DIRECTIONS if sum(map(abs, d)) == 2)
CORNERS = tuple(d for d in DIRECTIONS if sum(map(abs, d)) == 3)


@dataclasses.dataclass(frozen=True)
class FacesConfig:
    grid: Tuple[int, int, int] = (2, 2, 2)   # device grid (gx, gy, gz)
    points: Tuple[int, int, int] = (16, 16, 16)  # local block points
    dtype: str = "float32"
    granularity: str = "direct26"  # direct26 | staged3
    batched: bool = True           # one start per batch of sends
    pack: str = "jnp"              # jnp | pallas
    periodic: bool = False
    interior_compute: bool = True  # include the overlap kernel (step 4)
    # Relaxation factor applied to the whole field at the end of every
    # iteration (0 → off).  With 0 < damping < ~0.3 the combined
    # smooth + boundary-sum + scale update is a contraction, so the
    # field norm decays geometrically — the substrate for the
    # convergence-terminated (until-residual<tol) persistent loop.
    damping: float = 0.0

    @property
    def n_ranks(self) -> int:
        gx, gy, gz = self.grid
        return gx * gy * gz

    @property
    def n_points(self) -> int:
        return self.n_ranks * int(np.prod(self.points))


def _slab_index(side: int, n: int) -> Tuple[slice, ...]:
    """Boundary slab index along one axis: -1 → first plane, +1 → last,
    0 → everything."""
    if side == -1:
        return slice(0, 1)
    if side == 1:
        return slice(n - 1, n)
    return slice(0, n)


def _region_for(direction: Tuple[int, int, int], points) -> Tuple[slice, ...]:
    return tuple(_slab_index(s, n) for s, n in zip(direction, points))


def _slab_shape(direction, points) -> Tuple[int, ...]:
    return tuple(1 if s else n for s, n in zip(direction, points))


def _make_pack_fn(region, pack_mode: str):
    if pack_mode == "pallas":
        from repro.kernels import ops as kops

        def pack(u):  # u local view: (1,1,1,px,py,pz)
            return kops.halo_pack(u[0, 0, 0], region)[None, None, None]
    else:
        def pack(u):
            return u[0, 0, 0][region][None, None, None]
    return pack


def _make_unpack_fn(region, pack_mode: str):
    if pack_mode == "pallas":
        from repro.kernels import ops as kops

        def unpack(u, msg):
            return kops.halo_unpack_add(u[0, 0, 0], msg[0, 0, 0], region)[None, None, None]
    else:
        def unpack(u, msg):
            core = u[0, 0, 0]
            core = core.at[region].add(msg[0, 0, 0])
            return core[None, None, None]
    return unpack


def stencil_lowering(points, dtype) -> str:
    """Which lowering the interior stencil of a ``points`` block takes:
    ``"pallas"`` where the block tiles the one-pass kernel
    (:func:`repro.kernels.faces_stencil.stencil7_tiles`), else
    ``"jnp"``.  The shape alone decides; both compute one algorithm."""
    from repro.kernels.faces_stencil import stencil7_tiles

    return "pallas" if stencil7_tiles(tuple(points), dtype) else "jnp"


def _stencil7(core, lo, hi):
    """7-point periodic smoothing of ``core`` with x-ghost planes
    ``lo``/``hi``: the one-pass Pallas kernel where the block tiles it,
    else the jnp expression.  The x-neighbours are concat-with-ghost
    shifts (pure copies), and both lowerings add in the same order."""
    if stencil_lowering(core.shape, core.dtype) == "pallas":
        from repro.kernels import ops as kops

        return kops.faces_stencil7(core, lo, hi)
    xm = jnp.concatenate([lo, core[:-1]], axis=0)
    xp = jnp.concatenate([core[1:], hi], axis=0)
    return core + 0.125 * (
        xm + xp
        + jnp.roll(core, 1, 1) + jnp.roll(core, -1, 1)
        + jnp.roll(core, 1, 2) + jnp.roll(core, -1, 2)
        - 6.0 * core
    )


def _interior_fn(u):
    """Step-4 overlap kernel: a cheap local stencil on the interior,
    periodic in x through the block's own wrap planes."""
    core = u[0, 0, 0]
    return _stencil7(core, core[-1:], core[:1])[None, None, None]


def _with_stencil_arg(prog: STProgram, cfg: FacesConfig, points) -> STProgram:
    """``prog`` with the stencil's lowering as an argument of the
    engines' dispatch span (where the program has the stencil)."""
    if not cfg.interior_compute:
        return prog
    return dataclasses.replace(prog, span_args=(
        ("stencil", stencil_lowering(points, np.dtype(cfg.dtype))),))


def build_faces_program(cfg: FacesConfig, mesh,
                        name: Optional[str] = None,
                        coalesce: bool = True) -> STProgram:
    """Build the Faces inner-loop as an ST program on a (gx,gy,gz) mesh.

    ``name`` sets the program name (defaults to ``faces_{granularity}``)
    — composed programs (:func:`repro.core.schedule.compose`) need
    distinct names, since the name is the buffer namespace.

    With ``coalesce`` (default) the 26 direct26 messages are grouped at
    build time into ≤6 fused by-axis transfers — the paper's contiguous
    MPI buffer (§V-A) — with bit-identical results; pass ``False`` for
    the one-collective-per-neighbor lowering (A/B benchmarks).
    """
    gx, gy, gz = cfg.grid
    px, py, pz = cfg.points
    dtype = np.dtype(cfg.dtype)
    q = STQueue(mesh, name="faces")

    gshape = (gx, gy, gz, px, py, pz)
    q.buffer("u", gshape, dtype, pspec=AXES3)

    dirs = DIRECTIONS if cfg.granularity == "direct26" else FACES
    msg_in, msg_out = {}, {}
    for i, d in enumerate(dirs):
        sshape = _slab_shape(d, cfg.points)
        msg_out[d] = q.buffer(f"out{i}", (gx, gy, gz, *sshape), dtype, pspec=AXES3)
        msg_in[d] = q.buffer(f"in{i}", (gx, gy, gz, *sshape), dtype, pspec=AXES3)

    if cfg.granularity == "direct26":
        _emit_direct26(q, cfg, msg_in, msg_out)
    elif cfg.granularity == "staged3":
        _emit_staged3(q, cfg, msg_in, msg_out)
    else:
        raise ValueError(cfg.granularity)

    prog = q.build(name=name or f"faces_{cfg.granularity}", coalesce=coalesce)
    return _with_stencil_arg(prog, cfg, cfg.points)


def _emit_direct26(q: STQueue, cfg: FacesConfig, msg_in, msg_out):
    # NOTE: build_faces_part_program emits the same structure filtered
    # by direction ownership; the two must stay in lockstep (tag scheme,
    # recvs-before-sends order, global-direction unpack replay) for the
    # linked split's bit-identity with the full-domain run — enforced by
    # tests/test_links.py::test_linked_pipelined_bitmatches_full_domain.
    dirs = DIRECTIONS
    # 2. pack kernels (paper step 2; packs precede sends in stream order)
    for i, d in enumerate(dirs):
        region = _region_for(d, cfg.points)
        q.enqueue_kernel(_make_pack_fn(region, cfg.pack), ["u"], [msg_out[d]],
                         name=f"pack{i}")
    if cfg.batched:
        # 1+3. pre-post all receives, then all sends, one trigger for the
        # whole batch (the paper's batching semantics — one writeValue).
        for i, d in enumerate(dirs):
            peer = GridOffsetPeer(AXES3, tuple(-x for x in d), cfg.periodic)
            q.enqueue_recv(msg_in[d], peer, tag=i)
        for i, d in enumerate(dirs):
            q.enqueue_send(msg_out[d], GridOffsetPeer(AXES3, d, cfg.periodic), tag=i)
        q.enqueue_start()
    else:
        # unbatched: one writeValue (start) per message
        for i, d in enumerate(dirs):
            peer = GridOffsetPeer(AXES3, tuple(-x for x in d), cfg.periodic)
            q.enqueue_recv(msg_in[d], peer, tag=i)
            q.enqueue_send(msg_out[d], GridOffsetPeer(AXES3, d, cfg.periodic), tag=i)
            q.enqueue_start()
    # 4. interior compute overlapping communication (paper step 4)
    if cfg.interior_compute:
        q.enqueue_kernel(_interior_fn, ["u"], ["u"], name="interior")
    # 5. wait (paper step 5)
    q.enqueue_wait()
    # 6. unpack-and-add (paper step 6)
    for i, d in enumerate(dirs):
        region = _region_for(tuple(-x for x in d), cfg.points)
        q.enqueue_kernel(_make_unpack_fn(region, cfg.pack),
                         ["u", msg_in[d]], ["u"], name=f"unpack{i}")
    _emit_damping(q, cfg)


def _emit_staged3(q: STQueue, cfg: FacesConfig, msg_in, msg_out):
    """Beyond-paper: three axis sweeps.  Each sweep exchanges the two
    faces along one axis; because each sweep reads the ghost-updated
    block, edge and corner contributions propagate through the stages
    (standard staged halo).  6 messages instead of 26."""
    for stage, axis in enumerate((0, 1, 2)):
        dirs = [d for d in FACES if d[axis] != 0]
        for d in dirs:
            i = FACES.index(d)
            peer = GridOffsetPeer(AXES3, tuple(-x for x in d), cfg.periodic)
            q.enqueue_recv(msg_in[d], peer, tag=100 * stage + i)
        for d in dirs:
            i = FACES.index(d)
            region = _region_for(d, cfg.points)
            q.enqueue_kernel(_make_pack_fn(region, cfg.pack), ["u"], [msg_out[d]],
                             name=f"pack_s{stage}_{i}")
        for d in dirs:
            i = FACES.index(d)
            q.enqueue_send(msg_out[d], GridOffsetPeer(AXES3, d, cfg.periodic),
                           tag=100 * stage + i)
        q.enqueue_start()
        if cfg.interior_compute and stage == 0:
            q.enqueue_kernel(_interior_fn, ["u"], ["u"], name="interior")
        q.enqueue_wait()
        for d in dirs:
            region = _region_for(tuple(-x for x in d), cfg.points)
            q.enqueue_kernel(_make_unpack_fn(region, cfg.pack),
                             ["u", msg_in[d]], ["u"], name=f"unpack_s{stage}")
    _emit_damping(q, cfg)


def _emit_damping(q: STQueue, cfg: FacesConfig):
    """End-of-iteration relaxation kernel (only when cfg.damping is on)."""
    if cfg.damping:
        scale = float(cfg.damping)
        q.enqueue_kernel(lambda u: u * scale, ["u"], ["u"], name="damp")


# --------------------------------------------------------------------------
# persistent (device-resident) timed loop
# --------------------------------------------------------------------------


def global_residual_fn(cfg: FacesConfig, buf: str = "u"):
    """Build a ``reduce_fn(mem) -> scalar`` computing the *global* RMS
    norm of ``buf``: local sum of squares, ``lax.psum`` over the mesh
    axes, normalized by the global point count.  Runs inside the
    device-resident loop — the convergence residual with no host sync.
    """
    n_total = float(cfg.n_points)

    def residual(mem):
        local = jnp.sum(jnp.square(mem[buf].astype(jnp.float32)))
        return jnp.sqrt(jax.lax.psum(local, AXES3) / n_total)

    return residual


def run_faces_until_converged(cfg: FacesConfig, mesh, u0, tol: float,
                              max_iters: int, mode: str = "dataflow",
                              double_buffer: Optional[bool] = None,
                              donate: bool = True):
    """Iterate Faces until the global residual drops below ``tol`` —
    with the *device* deciding when to stop (ONE host dispatch).

    The termination predicate ``residual >= tol`` and the residual
    reduction both run inside the persistent engine's ``while_loop``;
    the host sees nothing until the converged field, the residual trace
    and the realized iteration count come back together.

    Returns ``(mem, residuals, n_done, stats)``: final buffers, the
    residual trace trimmed to the realized length, the realized
    iteration count, and the engine stats (``stats.dispatches == 1``).
    """
    from .engine_persistent import PersistentEngine

    prog = build_faces_program(cfg, mesh).persistent(
        max_iters, until=lambda r: r >= tol)
    eng = PersistentEngine(prog, mode=mode, double_buffer=double_buffer,
                           reduce_fn=global_residual_fn(cfg), donate=donate)
    mem, residuals, n_done = eng(eng.init_buffers({"u": u0}))
    n_done = int(n_done)
    return mem, np.asarray(residuals)[:n_done], n_done, eng.stats


def run_faces_persistent(cfg: FacesConfig, mesh, u0, n_iters: int,
                         mode: str = "dataflow", reduce_fn=None,
                         double_buffer: Optional[bool] = None,
                         donate: bool = True):
    """Run ``n_iters`` Faces iterations as ONE host dispatch.

    Builds the inner-loop ST program, marks it persistent, and executes
    it with :class:`~repro.core.engine_persistent.PersistentEngine` —
    the fully offloaded variant of the paper's timed loop (the host
    enqueues once; the device sequencer re-runs pack → trigger →
    exchange → wait → unpack N times).

    Returns ``(mem, stats)`` — final buffers and the engine's
    dispatch-counting stats (``stats.dispatches == 1`` however large
    ``n_iters`` is).  With ``reduce_fn`` set, returns
    ``((mem, reductions), stats)`` exactly as the engine does.
    """
    from .engine_persistent import PersistentEngine

    prog = build_faces_program(cfg, mesh).persistent(n_iters)
    eng = PersistentEngine(prog, mode=mode, reduce_fn=reduce_fn,
                           double_buffer=double_buffer, donate=donate)
    out = eng(eng.init_buffers({"u": u0}))
    return out, eng.stats


# --------------------------------------------------------------------------
# pipelined multi-queue loop (N x-split domain parts, one dispatch)
# --------------------------------------------------------------------------


def part_points(px: int, n: int) -> Tuple[int, ...]:
    """Sizes of an N-way (possibly uneven) split of ``px`` points.

    The first ``px % n`` parts take one extra plane (``numpy.array_split``
    convention), so odd-sized domains pipeline instead of erroring.
    """
    if not 1 <= n <= px:
        raise ValueError(
            f"cannot split {px} x-planes into {n} part(s): need "
            f"1 <= n_parts <= points[0]")
    base, extra = divmod(px, n)
    return tuple(base + (1 if k < extra else 0) for k in range(n))


def part_configs(cfg: FacesConfig, n: int) -> Tuple[FacesConfig, ...]:
    """Per-part FacesConfigs of an N-way x-split domain (same device
    grid); parts may be uneven — see :func:`part_points`."""
    px, py, pz = cfg.points
    return tuple(dataclasses.replace(cfg, points=(p, py, pz))
                 for p in part_points(px, n))


def split_parts(u0, n: int):
    """Split a (gx,gy,gz,px,py,pz) field into N x-parts (uneven OK)."""
    u0 = np.asarray(u0)
    sizes = part_points(u0.shape[3], n)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return [u0[:, :, :, offs[k]:offs[k + 1]] for k in range(n)]


def merge_parts(parts):
    """Inverse of :func:`split_parts`."""
    return jnp.concatenate([jnp.asarray(p) for p in parts], axis=3)


def half_config(cfg: FacesConfig, part: int = 0) -> FacesConfig:
    """The per-half FacesConfig of a 2-way x-split domain.

    For even ``points[0]`` both halves are identical; for odd sizes the
    halves are uneven (first half takes the extra plane) and ``part``
    selects which one — see :func:`part_configs` for the N-way form.
    """
    return part_configs(cfg, 2)[part]


def split_halves(u0):
    """Split a (gx,gy,gz,px,py,pz) field into two x-halves (uneven OK)."""
    return tuple(split_parts(u0, 2))


def merge_halves(ua, ub):
    """Inverse of :func:`split_halves`."""
    return merge_parts([ua, ub])


PIPELINE_NAMES = ("facesA", "facesB")


def part_names(n: int) -> Tuple[str, ...]:
    """Program names of an N-way split (2-way keeps the legacy pair)."""
    if n == 2:
        return PIPELINE_NAMES
    return tuple(f"faces{k}" for k in range(n))


# Ghost-plane exchange tags (cross-program, peer offset (0,0,0)):
# _GHOST_TAG_LO carries part k's LAST plane up into part k+1's "glo"
# slot; _GHOST_TAG_HI carries part k's FIRST plane down into part
# k-1's "ghi" slot (ring over the parts, matching the full block's
# local wrap-around stencil).
_GHOST_TAG_LO, _GHOST_TAG_HI = 0, 1


def _part_interior_fn(u, glo, ghi):
    """Step-4 overlap stencil of one x-part, ghost planes substituted.

    Bit-identical to :func:`_interior_fn` on the unsplit block: both
    call :func:`_stencil7`, here with the neighbour parts' planes
    exchanged this iteration as the ghosts (``glo`` the last plane of
    the part below, ``ghi`` the first plane of the part above, in a
    ring), there with the block's own wrap planes, so every float op
    matches the full-domain kernel's.
    """
    return _stencil7(u[0, 0, 0], glo[0, 0, 0], ghi[0, 0, 0])[None, None, None]


def build_faces_part_program(cfg: FacesConfig, mesh, part: int, n_parts: int,
                             names: Optional[Tuple[str, ...]] = None,
                             coalesce: bool = True) -> STProgram:
    """Build part ``part`` of an N-way x-split Faces domain, with
    cross-program links so the composed parts reproduce the FULL-domain
    iteration bit for bit (``cfg`` is the *full* domain's config).

    Two kinds of links (all declared via ``remote=`` and resolved by
    :func:`repro.core.schedule.compose`):

    * **ghost planes** — each part's interior stencil reads its ring
      neighbors' boundary planes (the full block's local wrap), fetched
      pre-iteration in a dedicated start/wait batch;
    * **x-crossing halo messages** — the 18 directions with an x
      component pack at one end of the split (part 0 for ``-x``, part
      N-1 for ``+x``), hop the device grid, and deposit into the
      *opposite end's* in-slots, whose unpack-adds replay in global
      direction order.  The 8 x-neutral directions stay per-part (each
      part exchanges exactly its own x-slice).

    The result must be composed with its sibling parts
    (``compose(*[build_faces_part_program(cfg, mesh, k, n) ...])``) —
    engines reject the open program.  Requires ``direct26`` granularity
    and batched triggering (the linked split is defined against that
    lowering).

    The emission below mirrors :func:`_emit_direct26` filtered by
    direction ownership; any structural change there (tags, recv/send
    order, unpack replay order) must be mirrored here — the bit-identity
    acceptance test fails loudly if the two drift.
    """
    if cfg.granularity != "direct26":
        raise ValueError(
            f"linked domain split supports granularity='direct26' only "
            f"(got {cfg.granularity!r})")
    if not cfg.batched:
        raise ValueError("linked domain split requires batched triggering")
    if n_parts < 2:
        raise ValueError("a linked split needs n_parts >= 2 "
                         "(use build_faces_program for the unsplit domain)")
    names = tuple(names) if names is not None else part_names(n_parts)
    if len(names) != n_parts:
        raise ValueError(f"need {n_parts} names, got {len(names)}")
    cfgp = part_configs(cfg, n_parts)[part]
    gx, gy, gz = cfg.grid
    px, py, pz = cfgp.points
    dtype = np.dtype(cfg.dtype)
    prev_name = names[(part - 1) % n_parts]
    next_name = names[(part + 1) % n_parts]

    # direction ownership under the split (see docstring)
    own = [d for d in DIRECTIONS if d[0] == 0]
    cross_out = [d for d in DIRECTIONS
                 if (d[0] == 1 and part == n_parts - 1)
                 or (d[0] == -1 and part == 0)]
    cross_in = [d for d in DIRECTIONS
                if (d[0] == 1 and part == 0)
                or (d[0] == -1 and part == n_parts - 1)]
    out_dst = {d: (names[0] if d[0] == 1 else names[n_parts - 1])
               for d in cross_out}
    in_src = {d: (names[n_parts - 1] if d[0] == 1 else names[0])
              for d in cross_in}

    q = STQueue(mesh, name=names[part])
    q.buffer("u", (gx, gy, gz, px, py, pz), dtype, pspec=AXES3)
    msg_in, msg_out = {}, {}
    for i, d in enumerate(DIRECTIONS):
        sshape = _slab_shape(d, cfgp.points)
        if d in own or d in cross_out:
            msg_out[d] = q.buffer(f"out{i}", (gx, gy, gz, *sshape), dtype,
                                  pspec=AXES3)
        if d in own or d in cross_in:
            msg_in[d] = q.buffer(f"in{i}", (gx, gy, gz, *sshape), dtype,
                                 pspec=AXES3)

    here = GridOffsetPeer(AXES3, (0, 0, 0))  # same-device cross-part hop
    if cfg.interior_compute:
        # ghost-plane ring exchange (dedicated batch: the stencil needs
        # the planes BEFORE the overlap kernel, so this one is waited
        # immediately — the compose interleave keeps each sender's
        # trigger ahead of this wait)
        q.buffer("glo", (gx, gy, gz, 1, py, pz), dtype, pspec=AXES3)
        q.buffer("ghi", (gx, gy, gz, 1, py, pz), dtype, pspec=AXES3)
        q.enqueue_recv("glo", here, tag=_GHOST_TAG_LO, remote=prev_name)
        q.enqueue_recv("ghi", here, tag=_GHOST_TAG_HI, remote=next_name)
        q.enqueue_send("u", here, tag=_GHOST_TAG_LO, remote=next_name,
                       region=(slice(0, 1),) * 3
                       + (slice(px - 1, px), slice(0, py), slice(0, pz)))
        q.enqueue_send("u", here, tag=_GHOST_TAG_HI, remote=prev_name,
                       region=(slice(0, 1),) * 3
                       + (slice(0, 1), slice(0, py), slice(0, pz)))
        q.enqueue_start()
        q.enqueue_wait()

    # 2. pack (own slabs + the x-crossing slabs this end owns)
    for i, d in enumerate(DIRECTIONS):
        if d in msg_out:
            region = _region_for(d, cfgp.points)
            q.enqueue_kernel(_make_pack_fn(region, cfg.pack), ["u"],
                             [msg_out[d]], name=f"pack{i}")
    # 1+3. pre-post all receives, then all sends, one trigger (batched)
    for i, d in enumerate(DIRECTIONS):
        if d not in msg_in:
            continue
        peer = GridOffsetPeer(AXES3, tuple(-x for x in d), cfg.periodic)
        q.enqueue_recv(msg_in[d], peer, tag=i,
                       remote=in_src.get(d))
    for i, d in enumerate(DIRECTIONS):
        if d not in msg_out:
            continue
        q.enqueue_send(msg_out[d], GridOffsetPeer(AXES3, d, cfg.periodic),
                       tag=i, remote=out_dst.get(d))
    q.enqueue_start()
    # 4. interior compute overlapping communication (ghost-substituted)
    if cfg.interior_compute:
        q.enqueue_kernel(_part_interior_fn, ["u", "glo", "ghi"], ["u"],
                         name="interior")
    # 5. wait
    q.enqueue_wait()
    # 6. unpack-and-add, replayed in GLOBAL direction order so the
    # add-accumulation order per element matches the unsplit program
    for i, d in enumerate(DIRECTIONS):
        if d not in msg_in:
            continue
        region = _region_for(tuple(-x for x in d), cfgp.points)
        q.enqueue_kernel(_make_unpack_fn(region, cfg.pack),
                         ["u", msg_in[d]], ["u"], name=f"unpack{i}")
    _emit_damping(q, cfg)
    prog = q.build(name=names[part], coalesce=coalesce)
    return _with_stencil_arg(prog, cfg, cfgp.points)


def run_faces_pipelined(cfg: FacesConfig, mesh, u0, *,
                        n_iters: Optional[int] = None,
                        tols: Optional[Tuple[float, ...]] = None,
                        max_iters: Optional[int] = None,
                        mode: str = "dataflow",
                        double_buffer: Optional[bool] = None,
                        donate: bool = True,
                        n_parts: int = 2,
                        exchange: bool = True,
                        tune: bool = False,
                        tune_space: Optional[Dict[str, Sequence]] = None,
                        tune_repeats: int = 3,
                        tune_measure_top: int = 3):
    """N x-split Faces queues, composed, iterated in ONE dispatch.

    The domain is split into ``n_parts`` x-parts (uneven sizes OK) on
    the *same* mesh; each part gets its own STQueue program, and
    :func:`repro.core.schedule.compose` fuses them so one part's packs
    and interior compute interleave with another's trigger→wait window
    — the pipelined multi-queue schedule, with the whole loop
    device-resident.

    With ``exchange=True`` (default) the parts are *linked*: they trade
    their shared interior faces (and the stencil's ghost planes) every
    iteration through cross-program channels, so the composed run is
    the TRUE full-domain solve — identical to the single-queue
    :func:`run_faces_persistent` on the whole domain, still one
    dispatch.  With ``exchange=False`` each part iterates independently
    (the PR-3 behaviour: N separate solves sharing a dispatch, each
    matching its own standalone run).  "Identical" is bit-exact in
    ``stream`` mode and in uncoalesced ``dataflow``; the default
    dataflow+coalesce lowering agrees to within a few ULPs (XLA FMA
    contraction differs between the two compilations — bounds and
    analysis in tests/test_links.py and tests/test_schedule.py).

    Two regimes:

    * ``n_iters=N`` — every part runs exactly N iterations (uniform
      fixed loop).  Returns ``(mem, stats)``; part k's field lives at
      ``mem[f"{part_names(n_parts)[k]}/u"]`` (see :func:`merge_parts`).
    * ``tols=(tol0, ..., tol{n-1})`` + ``max_iters`` — each part runs
      until its OWN subdomain residual drops below its own tolerance
      (device-decided, per-program predicates).  Returns
      ``(mem, residuals, n_done, stats)`` with ``residuals[name]``
      trimmed to the realized length and ``n_done[name]`` ints.  With
      ``exchange=False`` this is the bit-exact union of N independent
      :func:`run_faces_until_converged` runs; with ``exchange=True`` a
      converged part freezes while its neighbors keep reading its
      frozen boundary (the masked multi-queue loop), so the combined
      field is a staged, not simultaneous, solve.

    With ``tune=True`` the execution configuration is auto-tuned by
    :func:`repro.launch.tune.tune` before the real run: candidates over
    ``tune_space`` (default: interleave policy × trigger mode, seeded
    from the ``mode``/``double_buffer`` arguments) are priced by the
    cost model, the ``tune_measure_top`` cheapest are measured
    (``tune_repeats`` medians each), and the winner runs.  The knobs
    never change numerics — only lowering and schedule — so the
    returned fields are the same solve either way.  The return value
    grows a trailing :class:`~repro.launch.tune.TuneResult`:
    ``(mem, stats, tuned)`` / ``(mem, residuals, n_done, stats,
    tuned)``.
    """
    from .engine_persistent import PersistentEngine
    from .schedule import compose

    if (n_iters is None) == (tols is None):
        raise ValueError("pass exactly one of n_iters= or tols=")
    names = part_names(n_parts)
    cfgs = part_configs(cfg, n_parts)
    parts = split_parts(np.asarray(u0), n_parts)
    if exchange:
        # the x-crossing halo links tie the split's two ends; the
        # stencil's ghost-plane ring links every adjacent pair
        links = [(names[0], names[-1]), (names[-1], names[0])]
        if cfg.interior_compute:
            ring = [(names[k], names[(k + 1) % n_parts])
                    for k in range(n_parts)]
            links += ring + [(b, a) for a, b in ring]
        builders = [build_faces_part_program(cfg, mesh, k, n_parts,
                                             names=names)
                    for k in range(n_parts)]
        links = sorted(set(links))
    else:
        links = None
        builders = [build_faces_program(cfgs[k], mesh, name=names[k])
                    for k in range(n_parts)]
    init = {f"{nm}/u": p for nm, p in zip(names, parts)}

    if tols is None:
        progs = [b.persistent(n_iters) for b in builders]
        reduce_fns = None
    else:
        if max_iters is None:
            raise ValueError("tols= requires max_iters=")
        if len(tols) != n_parts:
            raise ValueError(
                f"tols needs one tolerance per part ({n_parts}), got {tols!r}")
        progs = [
            b.persistent(max_iters, until=lambda r, tol=tol: r >= tol)
            for b, tol in zip(builders, tols)
        ]
        reduce_fns = {nm: global_residual_fn(cfgk, buf=f"{nm}/u")
                      for nm, cfgk in zip(names, cfgs)}

    def make_engine(interleave=None, **engine_kw):
        sched = compose(*progs, links=links, interleave=interleave)
        kw = dict(mode=mode, double_buffer=double_buffer)
        kw.update(engine_kw)
        return PersistentEngine(sched, donate=donate,
                                reduce_fns=reduce_fns, **kw)

    tuned = None
    if tune:
        from repro.launch.tune import Knobs, tune as tune_search

        def build(knobs: "Knobs"):
            eng = make_engine(interleave=knobs.interleave_policy(),
                              **knobs.engine_kwargs())
            return eng, (lambda e=eng: e.init_buffers(init))

        tuned = tune_search(
            build,
            tune_space or {"interleave": ["round_robin", "sequential", 2],
                           "mode": ["dataflow", "stream"]},
            base=Knobs(mode=mode, double_buffer=double_buffer),
            repeats=tune_repeats, measure_top=tune_measure_top)
        eng = tuned.best.engine
        eng.stats.reset()  # returned stats cover the real solve only
    else:
        eng = make_engine()

    if tols is None:
        mem = eng(eng.init_buffers(init))
        return (mem, eng.stats, tuned) if tune else (mem, eng.stats)

    mem, reds, n_done = eng(eng.init_buffers(init))
    n_done = {nm: int(v) for nm, v in n_done.items()}
    reds = {nm: np.asarray(r)[: n_done[nm]] for nm, r in reds.items()}
    return ((mem, reds, n_done, eng.stats, tuned) if tune
            else (mem, reds, n_done, eng.stats))


# --------------------------------------------------------------------------
# NumPy oracle
# --------------------------------------------------------------------------


def faces_oracle(u: np.ndarray, cfg: FacesConfig) -> np.ndarray:
    """Reference update for one inner iteration, computed globally.

    ``u`` has shape (gx, gy, gz, px, py, pz).  Mirrors `direct26`
    semantics: interior stencil (if enabled) then the 26-direction
    boundary-sum, using the *pre-exchange* packed values (all packs
    happen before the interior kernel in stream order).
    """
    u = np.asarray(u, dtype=np.dtype(cfg.dtype))
    gx, gy, gz = cfg.grid
    out = u.copy()

    # packed messages are extracted from the original field
    packed = {
        d: u[(slice(None),) * 3 + _region_for(d, cfg.points)].copy()
        for d in DIRECTIONS
    }

    if cfg.interior_compute:
        core = out
        sm = core.copy()
        for ax in (3, 4, 5):
            sm += 0.125 * (np.roll(core, 1, ax) + np.roll(core, -1, ax))
        sm -= 0.125 * 6.0 * core
        out = sm

    for d in DIRECTIONS:
        # contribution sent by neighbor at -d arrives at my -d... each
        # rank r receives, from neighbor r - d, that neighbor's +d face,
        # deposited into r's -d region.  Global shift of packed slabs:
        msg = packed[d]
        shifted = np.zeros_like(msg)
        src = [slice(None)] * 6
        dst = [slice(None)] * 6
        ok = True
        for ax, delta, n in zip(range(3), d, (gx, gy, gz)):
            if delta == 0:
                continue
            if cfg.periodic:
                shifted_axis = None  # handled below with np.roll
            else:
                if delta > 0:
                    src[ax] = slice(0, n - delta)
                    dst[ax] = slice(delta, n)
                else:
                    src[ax] = slice(-delta, n)
                    dst[ax] = slice(0, n + delta)
        if cfg.periodic:
            shifted = np.roll(msg, shift=d, axis=(0, 1, 2))
        else:
            shifted[tuple(dst)] = msg[tuple(src)]
        region = _region_for(tuple(-x for x in d), cfg.points)
        out[(slice(None),) * 3 + region] += shifted
    if cfg.damping:
        out *= np.asarray(cfg.damping, dtype=out.dtype)
    return out
