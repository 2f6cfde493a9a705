"""Cross-program channels (links) — halo exchange BETWEEN composed queues.

Fast lane: static structure of ``compose(..., links=...)`` (matching,
Link metadata, per-pid completion wiring, trigger-before-wait
interleaving, the error surface), a tiny linked program on all three
engines, and the PR-5 acceptance contrast: an N-way linked
``run_faces_pipelined`` is bit-identical to the single-queue
full-domain ``run_faces_persistent`` — the composed run is the TRUE
full-domain solve in ONE dispatch, including odd (uneven) splits.

Slow lane: the same contrast on a real 2×2×2 8-device grid.
"""

import numpy as np
import pytest

from repro.core import (
    FacesConfig,
    FusedEngine,
    HostEngine,
    OffsetPeer,
    PersistentEngine,
    ScheduleError,
    STQueue,
    build_faces_part_program,
    compose,
    faces_oracle,
    merge_parts,
    part_names,
    run_faces_persistent,
    run_faces_pipelined,
)
from repro.core.descriptors import StartDesc, WaitDesc
from repro.core.halo import AXES3


def _mesh111():
    from repro.parallel import make_mesh
    return make_mesh((1, 1, 1), AXES3)


def _meshx():
    from repro.parallel import make_mesh
    return make_mesh((1,), ("x",))


def _u0(cfg, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randn(*cfg.grid, *cfg.points).astype(np.float32)


def _linked_pair(mesh):
    """A sends its buffer into B's slot; B doubles what it received."""
    qa = STQueue(mesh, name="A")
    qa.buffer("a", (4,), np.float32, pspec=("x",))
    qa.enqueue_send("a", OffsetPeer("x", 0, periodic=True), tag=7,
                    remote="B")
    qa.enqueue_start()
    qa.enqueue_wait()
    pa = qa.build()

    qb = STQueue(mesh, name="B")
    qb.buffer("slot", (4,), np.float32, pspec=("x",))
    qb.buffer("out", (4,), np.float32, pspec=("x",))
    qb.enqueue_recv("slot", OffsetPeer("x", 0, periodic=True), tag=7,
                    remote="A")
    qb.enqueue_start()
    qb.enqueue_wait()
    qb.enqueue_kernel(lambda s: s * 2.0, ["slot"], ["out"], name="double")
    pb = qb.build()
    return pa, pb


# -- structure ----------------------------------------------------------------


class TestLinkStructure:
    def test_open_links_counted_and_resolved(self):
        mesh = _meshx()
        pa, pb = _linked_pair(mesh)
        assert pa.open_links == 1 and pb.open_links == 1
        sched = compose(pa, pb)
        assert sched.open_links == 0
        assert len(sched.links) == 1
        l = sched.links[0]
        assert (l.src, l.dst, l.tag) == ("A", "B", 7)
        assert l.dst_buf == "B/slot"
        # the channel joined A's trigger batch, carrying B's pid
        ba = next(b for b in sched.batches if b.pid == 0)
        bb = next(b for b in sched.batches if b.pid == 1)
        cross = [c for c in ba.channels if c.dst_pid is not None]
        assert len(cross) == 1 and cross[0].dst_pid == 1
        assert cross[0].src_buf == "A/a" and cross[0].dst_buf == "B/slot"
        # ...and B's batch gates the deposit at its wait
        assert bb.cross_recv_bufs == ("B/slot",)
        assert all(c.dst_pid is None for c in bb.channels)

    def test_links_declaration_checked(self):
        mesh = _meshx()
        pa, pb = _linked_pair(mesh)
        sched = compose(pa, pb, links=[("A", "B")])
        assert len(sched.links) == 1
        pa, pb = _linked_pair(mesh)
        with pytest.raises(ScheduleError, match="links="):
            compose(pa, pb, links=[("A", "B"), ("B", "A")])

    def test_trigger_precedes_consumer_wait(self):
        """The interleaver must emit A's start before B's gating wait —
        for every resolved link (the deposit must already be in the
        stream when the consumer gates on it)."""
        mesh = _meshx()
        pa, pb = _linked_pair(mesh)
        sched = compose(pa, pb)
        descs = list(sched.descriptors)
        for l in sched.links:
            src_pid = sched.sub(l.src).pid
            dst_pid = sched.sub(l.dst).pid
            start_i = next(i for i, d in enumerate(descs)
                           if isinstance(d, StartDesc)
                           and d.pid == src_pid and d.batch == l.src_batch)
            wait_i = next((i for i, d in enumerate(descs)
                           if isinstance(d, WaitDesc)
                           and d.pid == dst_pid and d.batch >= l.dst_batch),
                          None)
            assert wait_i is None or start_i < wait_i

    def test_faces_part_links_structure(self):
        """The linked Faces split realizes the expected link topology:
        ghost ring between adjacent parts + x-crossing halos between
        the ends, triggers always ahead of the consumers' waits."""
        mesh = _mesh111()
        cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 3), periodic=True)
        n = 3
        names = part_names(n)
        progs = [build_faces_part_program(cfg, mesh, k, n).persistent(2)
                 for k in range(n)]
        sched = compose(*progs)
        pairs = {(l.src, l.dst) for l in sched.links}
        ring = {(names[k], names[(k + 1) % n]) for k in range(n)}
        ring |= {(b, a) for a, b in ring}
        ends = {(names[0], names[-1]), (names[-1], names[0])}
        assert pairs == ring | ends
        # 9 x-crossing directions each way + 2 ghost planes per ring edge
        n_cross = sum(1 for l in sched.links if l.dst_buf.endswith("glo")
                      or l.dst_buf.endswith("ghi"))
        assert n_cross == 2 * n
        assert len(sched.links) == 2 * n + 18
        # trigger-before-wait holds across the whole stream
        descs = list(sched.descriptors)
        for l in sched.links:
            src_pid, dst_pid = sched.sub(l.src).pid, sched.sub(l.dst).pid
            start_i = next(i for i, d in enumerate(descs)
                           if isinstance(d, StartDesc)
                           and d.pid == src_pid and d.batch == l.src_batch)
            wait_i = next(i for i, d in enumerate(descs)
                          if isinstance(d, WaitDesc)
                          and d.pid == dst_pid and d.batch >= l.dst_batch)
            assert start_i < wait_i, l


# -- error surface ------------------------------------------------------------


class TestLinkErrors:
    def test_engines_reject_open_program(self):
        mesh = _meshx()
        pa, _ = _linked_pair(mesh)
        for cls in (FusedEngine, HostEngine, PersistentEngine):
            with pytest.raises(ValueError, match="compose"):
                cls(pa)

    def test_remote_to_unknown_program(self):
        mesh = _meshx()
        pa, pb = _linked_pair(mesh)
        with pytest.raises(ScheduleError, match="unknown program"):
            compose(pa)  # peer 'B' missing from the composition

    def test_remote_to_self_rejected_at_build(self):
        from repro.core import QueueError
        mesh = _meshx()
        q = STQueue(mesh, name="A")
        q.buffer("a", (4,), np.float32, pspec=("x",))
        q.enqueue_send("a", OffsetPeer("x", 0, periodic=True), tag=0,
                       remote="A")
        q.enqueue_start()
        with pytest.raises(QueueError, match="itself"):
            q.build()

    def test_unmatched_cross_send(self):
        mesh = _meshx()
        pa, _ = _linked_pair(mesh)
        qb = STQueue(mesh, name="B")  # B posts no matching remote recv
        qb.buffer("slot", (4,), np.float32, pspec=("x",))
        with pytest.raises(ScheduleError, match="unmatched cross-program"):
            compose(pa, qb.build())

    def test_unwaited_cross_recv_rejected(self):
        """A remote receive whose batch is never waited has no gate to
        order the deposit against — compose must refuse it rather than
        let the consumer race the sender's trigger."""
        mesh = _meshx()
        qa = STQueue(mesh, name="A")
        qa.buffer("a", (4,), np.float32, pspec=("x",))
        qa.enqueue_send("a", OffsetPeer("x", 0, periodic=True), tag=0,
                        remote="B")
        qa.enqueue_start()
        qa.enqueue_wait()
        qb = STQueue(mesh, name="B")
        qb.buffer("slot", (4,), np.float32, pspec=("x",))
        qb.buffer("out", (4,), np.float32, pspec=("x",))
        qb.enqueue_recv("slot", OffsetPeer("x", 0, periodic=True), tag=0,
                        remote="A")
        qb.enqueue_start()  # no wait: the deposit is never gated
        qb.enqueue_kernel(lambda s: s * 2.0, ["slot"], ["out"], name="k")
        with pytest.raises(ScheduleError, match="no following enqueue_wait"):
            compose(qa.build(), qb.build())

    def test_link_cycle_detected(self):
        """Two programs whose gating waits each precede the other's
        trigger cannot be interleaved — a composition deadlock."""
        mesh = _meshx()

        def prog(name, peer):
            q = STQueue(mesh, name=name)
            q.buffer("a", (4,), np.float32, pspec=("x",))
            q.buffer("slot", (4,), np.float32, pspec=("x",))
            q.enqueue_recv("slot", OffsetPeer("x", 0, periodic=True), tag=0,
                           remote=peer)
            q.enqueue_start()
            q.enqueue_wait()      # gates on the peer's send...
            q.enqueue_send("a", OffsetPeer("x", 0, periodic=True), tag=0,
                           remote=peer)
            q.enqueue_start()     # ...which only triggers after our wait
            q.enqueue_wait()
            return q.build()

        with pytest.raises(ScheduleError, match="cycle"):
            compose(prog("A", "B"), prog("B", "A"))


# -- numerics (tiny linked program, all engines) ------------------------------


@pytest.mark.parametrize("engine_cls", [FusedEngine, HostEngine])
def test_tiny_link_deposits_across_programs(engine_cls):
    mesh = _meshx()
    pa, pb = _linked_pair(mesh)
    sched = compose(pa, pb)
    eng = engine_cls(sched)
    a = np.arange(4, dtype=np.float32)
    out = eng(eng.init_buffers({"A/a": a}))
    np.testing.assert_array_equal(np.asarray(out["B/slot"]), a)
    np.testing.assert_array_equal(np.asarray(out["B/out"]), 2.0 * a)


@pytest.mark.parametrize("mode", ["stream", "dataflow"])
def test_tiny_link_fused_modes(mode):
    mesh = _meshx()
    pa, pb = _linked_pair(mesh)
    sched = compose(pa, pb)
    eng = FusedEngine(sched, mode=mode)
    a = np.arange(4, dtype=np.float32) + 1.0
    out = eng(eng.init_buffers({"A/a": a}))
    np.testing.assert_array_equal(np.asarray(out["B/out"]), 2.0 * a)


# -- acceptance: linked N-way split == full-domain solve ----------------------


@pytest.mark.parametrize("n_parts,points", [
    (2, (6, 4, 3)),
    (2, (5, 4, 3)),   # odd: uneven halves (3, 2) pipeline instead of erroring
    (3, (7, 3, 4)),   # uneven three-way (3, 2, 2)
    (4, (6, 3, 3)),   # parts of a single plane each ride along too
])
def test_linked_pipelined_bitmatches_full_domain(n_parts, points):
    """THE acceptance contrast: the linked composed run IS the
    full-domain run — bit-identical in stream mode (and uncoalesced
    dataflow), one dispatch.  Default dataflow+coalesce drifts only by
    the documented FMA-contraction ULPs (see test_schedule's slow lane)
    and must stay within 4 ULP x n_iters."""
    n = 3
    cfg = FacesConfig(grid=(1, 1, 1), points=points, periodic=True)
    mesh = _mesh111()
    u0 = _u0(cfg, seed=11)
    names = part_names(n_parts)

    # stream mode: bit-identical
    full, _ = run_faces_persistent(cfg, mesh, u0, n_iters=n, mode="stream")
    mem, stats = run_faces_pipelined(cfg, mesh, u0, n_iters=n,
                                     n_parts=n_parts, mode="stream")
    assert stats.dispatches == 1 and stats.sync_points == 0
    got = np.asarray(merge_parts([mem[f"{nm}/u"] for nm in names]))
    np.testing.assert_array_equal(got, np.asarray(full["u"]))

    # ...and against the NumPy oracle (the exchange is a real solve)
    ref = u0
    for _ in range(n):
        ref = faces_oracle(ref, cfg)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)

    # dataflow + coalesced (the default fast path): documented ULP bound
    fulld, _ = run_faces_persistent(cfg, mesh, u0, n_iters=n,
                                    mode="dataflow")
    memd, statsd = run_faces_pipelined(cfg, mesh, u0, n_iters=n,
                                       n_parts=n_parts, mode="dataflow")
    assert statsd.dispatches == 1
    gotd = np.asarray(merge_parts([memd[f"{nm}/u"] for nm in names]))
    np.testing.assert_array_max_ulp(gotd, np.asarray(fulld["u"]),
                                    maxulp=4 * n)


def test_linked_pipelined_uncoalesced_dataflow_exact():
    """With coalescing off the dataflow comparison is exact too — the
    ULP drift is strictly a property of the fused-transfer lowering."""
    from repro.core.halo import split_parts

    cfg = FacesConfig(grid=(1, 1, 1), points=(5, 4, 3), periodic=True)
    mesh = _mesh111()
    u0 = _u0(cfg, seed=12)
    n_parts, n = 2, 3
    names = part_names(n_parts)

    from repro.core import build_faces_program
    full = build_faces_program(cfg, mesh).persistent(n)
    ef = PersistentEngine(full, mode="dataflow", coalesce=False)
    want = np.asarray(ef(ef.init_buffers({"u": u0}))["u"])

    progs = [build_faces_part_program(cfg, mesh, k, n_parts).persistent(n)
             for k in range(n_parts)]
    eng = PersistentEngine(compose(*progs), mode="dataflow", coalesce=False)
    mem = eng(eng.init_buffers(
        {f"{nm}/u": p for nm, p in zip(names, split_parts(u0, n_parts))}))
    got = np.asarray(merge_parts([mem[f"{nm}/u"] for nm in names]))
    np.testing.assert_array_equal(got, want)


def test_linked_single_pass_engines_match_full_program():
    """One interpreted pass of the linked composition equals one pass of
    the full program — fused and host engines alike."""
    cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 3), periodic=True)
    mesh = _mesh111()
    u0 = _u0(cfg, seed=13)
    n_parts = 3
    names = part_names(n_parts)
    from repro.core import build_faces_program
    from repro.core.halo import split_parts

    full_prog = build_faces_program(cfg, mesh)
    progs = [build_faces_part_program(cfg, mesh, k, n_parts)
             for k in range(n_parts)]
    sched = compose(*progs)
    for cls, kw in ((FusedEngine, {"mode": "stream"}), (HostEngine, {})):
        ref_eng = cls(full_prog, **kw)
        want = np.asarray(ref_eng(ref_eng.init_buffers({"u": u0}))["u"])
        eng = cls(sched, **kw)
        mem = eng(eng.init_buffers(
            {f"{nm}/u": p for nm, p in zip(names, split_parts(u0, n_parts))}))
        got = np.asarray(merge_parts([mem[f"{nm}/u"] for nm in names]))
        np.testing.assert_array_equal(got, want, err_msg=cls.__name__)


def test_linked_pipelined_with_tolerances_freezes_parts():
    """Per-part predicates still work under links: a converged part
    freezes while its neighbor keeps reading the frozen boundary
    (masked multi-queue loop), one dispatch throughout.

    Two regimes are pinned: with equal tolerances both parts converge
    normally; with a much tighter tolerance on one part, the other
    part's frozen boundary keeps injecting energy every iteration, so
    the tight part's residual plateaus at a nonzero fixed point and it
    runs to the max_iters bound — linked parts are a COUPLED system,
    not N independent solves."""
    cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 4), periodic=True,
                      damping=0.12)
    mesh = _mesh111()
    u0 = _u0(cfg, seed=14)

    mem, reds, n_done, stats = run_faces_pipelined(
        cfg, mesh, u0, tols=(1e-1, 1e-1), max_iters=50)
    assert stats.dispatches == 1 and stats.sync_points == 0
    for nm in part_names(2):
        assert 1 <= n_done[nm] < 50
        assert reds[nm][-1] < 1e-1 <= reds[nm][:-1].min()

    mem, reds, n_done, stats = run_faces_pipelined(
        cfg, mesh, u0, tols=(1e-1, 1e-3), max_iters=50)
    assert stats.dispatches == 1
    assert n_done["facesA"] < 50 and reds["facesA"][-1] < 1e-1
    # the tight part hits the bound: its residual floor is set by the
    # frozen neighbor's boundary injection, well above its tolerance
    assert n_done["facesB"] == 50
    assert reds["facesB"][-1] >= 1e-3
    np.testing.assert_allclose(reds["facesB"][-1], reds["facesB"][-5],
                               rtol=1e-3)  # plateaued, not diverging


def test_linked_requires_direct26_and_batched():
    cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 3),
                      granularity="staged3")
    with pytest.raises(ValueError, match="direct26"):
        build_faces_part_program(cfg, _mesh111(), 0, 2)
    cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 3), batched=False)
    with pytest.raises(ValueError, match="batched"):
        build_faces_part_program(cfg, _mesh111(), 0, 2)
    cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 3))
    with pytest.raises(ValueError, match="n_parts"):
        build_faces_part_program(cfg, _mesh111(), 0, 1)


def test_linked_no_interior_compute():
    """interior_compute=False drops the ghost ring (only the x-crossing
    links remain) and still bit-matches the full-domain run."""
    cfg = FacesConfig(grid=(1, 1, 1), points=(6, 3, 3), periodic=True,
                      interior_compute=False)
    mesh = _mesh111()
    u0 = _u0(cfg, seed=15)
    names = part_names(2)
    full, _ = run_faces_persistent(cfg, mesh, u0, n_iters=2, mode="stream")
    mem, stats = run_faces_pipelined(cfg, mesh, u0, n_iters=2, n_parts=2,
                                     mode="stream")
    assert stats.dispatches == 1
    got = np.asarray(merge_parts([mem[f"{nm}/u"] for nm in names]))
    np.testing.assert_array_equal(got, np.asarray(full["u"]))


def test_part_stencils_bitmatch_the_whole_block_on_the_kernel():
    """The linked split's stencil where the block tiles the Pallas
    kernel (interpreted here): two x-halves, each given the other's
    planes as ghosts, are bit for bit the whole block's stencil."""
    import jax.numpy as jnp

    from repro.core.halo import (_interior_fn, _part_interior_fn,
                                 stencil_lowering)

    shape, half = (16, 8, 128), 8
    assert stencil_lowering(shape, np.float32) == "pallas"
    assert stencil_lowering((half,) + shape[1:], np.float32) == "pallas"
    u = jnp.asarray(np.random.RandomState(5).randn(1, 1, 1, *shape),
                    jnp.float32)
    a, b = u[:, :, :, :half], u[:, :, :, half:]
    # a ring of two: each half's ghosts are the other's end planes
    pa = _part_interior_fn(a, b[:, :, :, -1:], b[:, :, :, :1])
    pb = _part_interior_fn(b, a[:, :, :, -1:], a[:, :, :, :1])
    np.testing.assert_array_equal(np.concatenate([pa, pb], axis=3),
                                  np.asarray(_interior_fn(u)))


# -- multi-device matrix (subprocess, slow lane) ------------------------------


@pytest.mark.slow
def test_linked_pipelined_matches_full_domain_8dev(subproc):
    r = subproc("""
import numpy as np
from repro.core import (FacesConfig, run_faces_persistent,
                        run_faces_pipelined, merge_parts, part_names)
from repro.parallel import make_mesh

mesh = make_mesh((2, 2, 2), ("gx", "gy", "gz"))
cfg = FacesConfig(grid=(2, 2, 2), points=(6, 4, 4))
u0 = np.random.RandomState(0).randn(2, 2, 2, 6, 4, 4).astype(np.float32)
N = 3

for n_parts in (2, 3):
    names = part_names(n_parts)
    # stream mode: the linked composed run IS the full-domain run, bit
    # for bit, on the real 8-device grid (x-crossing halos hop devices)
    full, _ = run_faces_persistent(cfg, mesh, u0, n_iters=N, mode="stream")
    mem, stats = run_faces_pipelined(cfg, mesh, u0, n_iters=N,
                                     n_parts=n_parts, mode="stream")
    assert stats.dispatches == 1
    got = np.asarray(merge_parts([mem[f"{nm}/u"] for nm in names]))
    np.testing.assert_array_equal(got, np.asarray(full["u"]))

    # dataflow default: only the documented coalesced-lowering FMA
    # drift (see tests/test_schedule.py slow lane) — a few eps per
    # element per iteration, amplified by the boundary accumulation;
    # rtol=1e-5 (~80 eps) holds with headroom on the 8-device grid
    fulld, _ = run_faces_persistent(cfg, mesh, u0, n_iters=N,
                                    mode="dataflow")
    memd, statsd = run_faces_pipelined(cfg, mesh, u0, n_iters=N,
                                       n_parts=n_parts, mode="dataflow")
    assert statsd.dispatches == 1
    gotd = np.asarray(merge_parts([memd[f"{nm}/u"] for nm in names]))
    np.testing.assert_allclose(gotd, np.asarray(fulld["u"]),
                               rtol=1e-5, atol=1e-6)
    print(f"n_parts={n_parts} OK")
print("linked 8dev OK")
""")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "linked 8dev OK" in r.stdout
