"""Faces microbenchmark — the paper's §V experiments (Figs. 8–12).

Reproduces each figure's *experimental contrast* on the CPU-device grid
(absolute Slingshot timings need the NIC; the control-path contrasts do
not):

fig8   64×1×1-style 1-D multi-rank: baseline (host-orchestrated, batch
       sync) vs ST-emulated (host engine, per-op sync — the progress-
       thread tax) vs ST-offloaded (fused).  Paper: ST 10% *slower*
       when the progress thread dominates.
fig9   single-node intra: baseline vs progress-thread emulation.
       Paper: ST 4% slower.
fig10  1 rank/node 1-D: baseline vs fully-offloaded ST.  Paper: parity.
fig11  2×2×2 3-D (26 neighbors): same A/B.  Paper: ST +4% — the win
       grows with message count because each message costs the host a
       dispatch but costs the fused program nothing.
fig12  trigger tuning: stock stream-memory ops (ST `stream` mode,
       strict FIFO barriers) vs hand-tuned shaders (ST `dataflow` mode,
       minimal ordering).  Paper: +8% over baseline.
figP   persistent iteration loop (beyond-paper; the "fully offloaded"
       follow-up): host per-op vs fused per-iteration vs persistent
       (device-resident fori_loop) — the host-dispatch count for the
       whole N-iteration timed loop collapses from N×per-op and N×1
       down to exactly 1, measured via HostStats counters.
fig_pipeline  multi-queue composition (beyond-paper; the multi-DWQ
       schedule): two half-grid Faces queues run sequentially (two
       persistent dispatches, no cross-queue overlap) vs composed via
       ``repro.core.schedule.compose`` (ONE dispatch, round-robin
       interleaved) — reports the overlap speedup and dispatch counts.

Loop configuration mirrors the paper (§V-B): outer × middle × inner
with buffer alloc in the outer loop; defaults are scaled down for CPU
(env FACES_INNER etc. override).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

RESULTS: List[Dict] = []

# tuner-chosen knobs per published row ("bench/variant" -> knob dict);
# benchmarks/run.py stamps this into BENCH_faces.json's _meta so the
# perf gate pins the choices and flags drift on re-tune
TUNED_KNOBS: Dict[str, Dict] = {}


def _cfg_env(name, default):
    return int(os.environ.get(name, default))


def _time_engine(engine, mem, inner: int, repeats: int = 5, fresh=None):
    """Time ``inner`` chained engine calls, ``repeats`` times.

    ``fresh`` (a zero-arg factory) re-materializes the input buffers
    before each repeat *outside* the timed section — required for
    donating engines, whose calls consume their inputs (the ``m =
    engine(m)`` chain donates every intermediate, which is the point).

    The loop itself is the tuner's (:func:`repro.launch.tune.measure`)
    — one timing implementation for benches and auto-tuning; callers
    here warm their engines explicitly, so warmup is skipped.
    """
    from repro.launch.tune import measure
    return measure(engine,
                   fresh if fresh is not None else (lambda: dict(mem)),
                   inner, repeats, warm=False)


def _setup(grid, points, **cfg_kw):
    import jax
    from repro.core import FacesConfig, FusedEngine, HostEngine, build_faces_program
    from repro.parallel import make_mesh

    mesh = make_mesh(grid, ("gx", "gy", "gz"))
    cfg = FacesConfig(grid=grid, points=points, **cfg_kw)
    prog = build_faces_program(cfg, mesh)
    rng = np.random.RandomState(0)
    u0 = rng.randn(*grid, *points).astype(np.float32)
    return cfg, prog, u0


def _variants(prog, u0, inner, which=("baseline", "st_emulated", "st_offload")):
    from repro.core import FusedEngine, HostEngine

    out = {}
    # ST engines donate their inputs: the m = engine(m) timed chain then
    # rotates buffers zero-copy across dispatches (host baselines keep
    # the conventional copy-per-dispatch behaviour they model)
    specs = {
        "baseline": (HostEngine, {"sync": "batch"}, prog.dispatch_count_host()),
        "st_emulated": (HostEngine, {"sync": "every_op"},
                        prog.dispatch_count_host()),
        "st_offload": (FusedEngine, {"mode": "stream", "donate": True}, 1),
        "st_tuned": (FusedEngine, {"mode": "dataflow", "donate": True}, 1),
    }
    for name in which:
        cls, kw, n_disp = specs[name]
        eng = cls(prog, **kw)
        fresh = (lambda e=eng: e.init_buffers({"u": u0}))
        eng(fresh())  # warm every per-descriptor/fused compile
        donating = kw.get("donate", False)
        r = _time_engine(eng, None if donating else fresh(), inner,
                         fresh=fresh if donating else None)
        r["dispatches_per_iter"] = n_disp
        out[name] = r
    return out


def _report(fig: str, variants: Dict, paper_claim: str):
    base = variants.get("baseline", {}).get("avg_s")
    for name, r in variants.items():
        rel = (r["avg_s"] / base) if base else float("nan")
        derived = (f"rel_to_baseline={rel:.3f};"
                   f"dispatches={r['dispatches_per_iter']}")
        if r.get("note"):
            derived += f";{r['note']}"
        RESULTS.append({
            "bench": f"faces_{fig}", "variant": name,
            "us_per_call": r["avg_s"] * 1e6,
            "median_ms": r["med_s"] * 1e3,
            "dispatches": r["dispatches_per_iter"],
            "derived": derived,
        })
        print(f"  {fig:6s} {name:12s} avg={r['avg_s']*1e3:9.2f}ms "
              f"min={r['min_s']*1e3:9.2f}ms rel={rel:6.3f} "
              f"dispatch/iter={r['dispatches_per_iter']}")
    print(f"  paper: {paper_claim}")


def fig8(inner=None):
    """8 ranks 1-D, many messages per rank, progress-thread emulation."""
    inner = inner or _cfg_env("FACES_INNER", 10)
    _, prog, u0 = _setup((8, 1, 1), (12, 12, 12))
    v = _variants(prog, u0, inner)
    _report("fig8", v, "ST 10% slower than baseline (progress-thread tax)")
    return v


def fig9(inner=None):
    """Intra-node: baseline vs per-op progress thread."""
    inner = inner or _cfg_env("FACES_INNER", 10)
    _, prog, u0 = _setup((8, 1, 1), (12, 12, 12))
    v = _variants(prog, u0, inner, which=("baseline", "st_emulated"))
    _report("fig9", v, "ST 4% slower (progress thread per MPI process)")
    return v


def fig10(inner=None):
    """1-D, full NIC offload: parity or better."""
    inner = inner or _cfg_env("FACES_INNER", 10)
    _, prog, u0 = _setup((8, 1, 1), (12, 12, 12))
    v = _variants(prog, u0, inner, which=("baseline", "st_offload"))
    _report("fig10", v, "ST ≈ parity with baseline (HW offload)")
    return v


def fig11(inner=None):
    """2×2×2 3-D (26 neighbors): offload advantage grows."""
    inner = inner or _cfg_env("FACES_INNER", 10)
    _, prog, u0 = _setup((2, 2, 2), (12, 12, 12))
    v = _variants(prog, u0, inner, which=("baseline", "st_offload"))
    _report("fig11", v, "ST 4% faster (NIC offload, more messages)")
    return v


def fig12(inner=None):
    """Trigger tuning: strict stream-memory ops vs relaxed triggers."""
    from repro.core import FusedEngine
    from repro.launch.tune import tune

    inner = inner or _cfg_env("FACES_INNER", 10)
    _, prog, u0 = _setup((2, 2, 2), (12, 12, 12))
    v = _variants(prog, u0, inner, which=("baseline",))
    # st_tuned is an *auto-tuner*: the generic searcher
    # (repro.launch.tune) measures the trigger-ordering knob space and
    # publishes the best knob for this platform rather than pinning
    # `dataflow` — if strict stream ordering measured faster here, that
    # IS the tuned setting (the paper's hand-tuned shaders played the
    # same game on the NIC side).  Both candidates' measurements become
    # the rows directly: st_offload is the stream candidate, and the
    # raw dataflow measurement stays tracked as its own variant so a
    # dataflow-mode regression remains visible in the trajectory even
    # when the stream fallback hides it from the published st_tuned
    # number.

    def build(knobs):
        eng = FusedEngine(prog, mode=knobs.mode, donate=True,
                          coalesce=knobs.coalesce)
        return eng, (lambda e=eng: e.init_buffers({"u": u0}))

    res = tune(build, {"mode": ["stream", "dataflow"]}, inner=inner,
               repeats=5, measure_top=2, engine_kind="fused")
    by_mode = {c.knobs.mode: c for c in res.measured}
    v["st_offload"] = dict(by_mode["stream"].stats, dispatches_per_iter=1)
    v["st_tuned_raw"] = dict(by_mode["dataflow"].stats,
                             dispatches_per_iter=1, note="knob=dataflow_raw")
    best_mode = res.best.knobs.mode
    v["st_tuned"] = dict(
        res.best.stats, dispatches_per_iter=1,
        note=f"knob={'dataflow' if best_mode == 'dataflow' else 'stream_fallback'}")
    TUNED_KNOBS["faces_fig12/st_tuned"] = res.knobs_dict()
    _report("fig12", v, "ST-shader 8% faster than baseline (tuned triggers)")
    return v


def fig_persistent(inner=None):
    """Persistent loop: N iterations as ONE dispatch (vs N, vs N×per-op)."""
    inner = inner or _cfg_env("FACES_INNER", 10)
    from repro.core import FusedEngine, HostEngine, PersistentEngine

    _, prog, u0 = _setup((2, 2, 2), (12, 12, 12))
    pprog = prog.persistent(inner)
    repeats = 5
    rows = {}

    # host per-op: every descriptor its own dispatch, each iteration
    host = HostEngine(prog, sync="every_op")
    mem = host.init_buffers({"u": u0})
    host(dict(mem))  # warm per-descriptor compiles
    host.stats.reset()
    rows["host_per_op"] = _time_engine(host, mem, inner, repeats)
    rows["host_per_op"]["dispatches_per_loop"] = host.stats.dispatches // repeats

    # fused: one dispatch per iteration (donated: buffers rotate
    # zero-copy across the chained dispatches)
    fused = FusedEngine(prog, mode="dataflow", donate=True)
    fresh_f = lambda: fused.init_buffers({"u": u0})
    fused(fresh_f())  # warm
    fused.stats.reset()
    rows["fused_per_iter"] = _time_engine(fused, None, inner, repeats,
                                          fresh=fresh_f)
    rows["fused_per_iter"]["dispatches_per_loop"] = fused.stats.dispatches // repeats

    # persistent: ONE dispatch for the whole inner loop
    pers = PersistentEngine(pprog, mode="dataflow", donate=True)
    fresh_p = lambda: pers.init_buffers({"u": u0})
    pers(fresh_p())  # warm
    pers.stats.reset()
    rows["persistent"] = _time_engine(pers, None, 1, repeats,  # 1 call = inner iters
                                      fresh=fresh_p)
    rows["persistent"]["dispatches_per_loop"] = pers.stats.dispatches // repeats

    base = rows["host_per_op"]["avg_s"]
    for name, r in rows.items():
        rel = r["avg_s"] / base if base else float("nan")
        RESULTS.append({
            "bench": "faces_figP", "variant": name,
            "us_per_call": r["avg_s"] * 1e6,
            "median_ms": r["med_s"] * 1e3,
            "dispatches": r["dispatches_per_loop"],
            "derived": f"rel_to_host={rel:.3f};"
                       f"dispatches_per_loop={r['dispatches_per_loop']}",
        })
        print(f"  figP   {name:14s} avg={r['avg_s']*1e3:9.2f}ms "
              f"rel={rel:6.3f} dispatch/loop={r['dispatches_per_loop']}")
    assert rows["persistent"]["dispatches_per_loop"] == 1
    print(f"  contrast: {inner} iterations cost the host "
          f"{rows['host_per_op']['dispatches_per_loop']} dispatches, the fused "
          f"engine {rows['fused_per_iter']['dispatches_per_loop']}, the "
          f"persistent engine 1 (device-resident loop)")
    return rows


def fig_convergence(tols=(1e-1, 1e-2, 1e-3), max_iters=None):
    """Convergence loop: host-polled stopping vs device-resident while_loop."""
    import jax
    import jax.numpy as jnp
    from repro.core import (
        FacesConfig, FusedEngine, PersistentEngine, build_faces_program,
        global_residual_fn,
    )
    from repro.parallel import make_mesh

    max_iters = max_iters or _cfg_env("FACES_MAX_ITERS", 64)
    grid, points = (2, 2, 2), (12, 12, 12)
    mesh = make_mesh(grid, ("gx", "gy", "gz"))
    # damping=0.12 makes the damped Faces update a contraction on this
    # grid: tols (1e-1, 1e-2, 1e-3) realize ~1 / 3 / 11 iterations
    cfg = FacesConfig(grid=grid, points=points, damping=0.12)
    rng = np.random.RandomState(0)
    u0 = rng.randn(*grid, *points).astype(np.float32)
    residual = global_residual_fn(cfg)

    # host-polled baseline: one dispatch per iteration, and the host
    # fetches the residual after EACH iteration to decide whether to
    # stop — the control-path round-trip the ST model removes.
    prog = build_faces_program(cfg, mesh)
    fused = FusedEngine(prog, mode="dataflow")
    poll = jax.jit(
        lambda u: jnp.sqrt(jnp.sum(jnp.square(u.astype(jnp.float32)))
                           / cfg.n_points))

    for tol in tols:
        # device-resident: the while_loop owns termination (ONE dispatch)
        pprog = build_faces_program(cfg, mesh).persistent(
            max_iters, until=lambda r, tol=tol: r >= tol)
        pers = PersistentEngine(pprog, mode="dataflow", reduce_fn=residual,
                                donate=True)

        # warm every compile outside the timed sections
        mem = fused.init_buffers({"u": u0})
        fused(dict(mem))
        float(poll(mem["u"]))
        pers(pers.init_buffers({"u": u0}))

        fused.stats.reset()
        t0 = time.perf_counter()
        mem = fused.init_buffers({"u": u0})
        host_iters = 0
        while host_iters < max_iters:
            mem = fused(mem)
            host_iters += 1
            if float(poll(mem["u"])) < tol:  # host sync, every iteration
                break
        host_s = time.perf_counter() - t0
        host_dispatches = fused.stats.dispatches

        pers.stats.reset()
        mem0 = pers.init_buffers({"u": u0})
        t0 = time.perf_counter()
        _, res, n_done = pers(mem0)
        n_done = int(n_done)  # the single host read, after convergence
        dev_s = time.perf_counter() - t0

        # the two residuals are differently-ordered float reductions
        # (sharded psum vs one host-side sum): allow a one-iteration
        # disagreement at a tolerance boundary
        assert pers.stats.dispatches == 1 and abs(n_done - host_iters) <= 1, (
            pers.stats.dispatches, n_done, host_iters)
        for name, secs, iters, disp, syncs in (
                ("host_polled", host_s, host_iters, host_dispatches,
                 host_iters),
                ("device_resident", dev_s, n_done, 1, 0)):
            RESULTS.append({
                "bench": "faces_convergence", "variant": f"{name}_tol{tol:g}",
                "us_per_call": secs * 1e6,
                "median_ms": secs * 1e3,
                "dispatches": disp,
                "derived": f"tol={tol:g};iters={iters};dispatches={disp};"
                           f"host_syncs={syncs}",
            })
            print(f"  conv   tol={tol:<7g} {name:16s} iters={iters:3d} "
                  f"dispatches={disp:3d} host_syncs={syncs:3d} "
                  f"wall={secs*1e3:8.2f}ms")
    return RESULTS


def fig_pipeline(inner=None, repeats=5):
    """Pipelined multi-queue: 2 composed half-grid queues, 1 dispatch,
    vs the same two persistent programs dispatched sequentially (2) —
    plus the LINKED N-way rows: cross-program channels make the
    composed parts exchange their shared faces, so the composed run is
    the TRUE full-domain solve (verified against the single-queue
    full-domain run) while still costing one dispatch."""
    import jax
    from repro.core import (
        FacesConfig, PersistentEngine, build_faces_program,
        build_faces_part_program, compose, half_config, merge_parts,
        part_names, split_halves, split_parts,
    )
    from repro.parallel import make_mesh

    inner = inner or _cfg_env("FACES_INNER", 10)
    grid, points = (2, 2, 2), (12, 12, 12)
    mesh = make_mesh(grid, ("gx", "gy", "gz"))
    cfg = FacesConfig(grid=grid, points=points)
    cfgh = half_config(cfg)
    rng = np.random.RandomState(0)
    u0 = rng.randn(*grid, *points).astype(np.float32)
    ua, ub = split_halves(u0)

    progA = build_faces_program(cfgh, mesh, name="facesA").persistent(inner)
    progB = build_faces_program(cfgh, mesh, name="facesB").persistent(inner)
    engA = PersistentEngine(progA, mode="dataflow", donate=True)
    engB = PersistentEngine(progB, mode="dataflow", donate=True)
    freshA = lambda: engA.init_buffers({"u": ua})
    freshB = lambda: engB.init_buffers({"u": ub})
    outA, outB = engA(freshA()), engB(freshB())  # warm compiles

    # sequential: two host dispatches per loop, no cross-queue overlap
    engA.stats.reset(), engB.stats.reset()
    times = []
    for _ in range(repeats):
        memA, memB = freshA(), freshB()
        t0 = time.perf_counter()
        outA, outB = engA(memA), engB(memB)
        jax.block_until_ready([list(outA.values()), list(outB.values())])
        times.append(time.perf_counter() - t0)
    seq = {"avg_s": float(np.mean(times)), "med_s": float(np.median(times)),
           "min_s": float(np.min(times))}
    seq_disp = (engA.stats.dispatches + engB.stats.dispatches) // repeats

    # composed (unlinked): ONE dispatch, B's compute interleaves A's
    # comm windows, each half still an independent solve
    sched = compose(progA, progB)
    engC = PersistentEngine(sched, mode="dataflow", donate=True)
    freshC = lambda: engC.init_buffers({"facesA/u": ua, "facesB/u": ub})
    warm = engC(freshC())
    # the composition must not perturb either queue's numerics
    np.testing.assert_allclose(np.asarray(warm["facesA/u"]),
                               np.asarray(outA["u"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(warm["facesB/u"]),
                               np.asarray(outB["u"]), rtol=1e-5, atol=1e-6)
    engC.stats.reset()
    comp = _time_engine(engC, None, 1, repeats, fresh=freshC)
    comp_disp = engC.stats.dispatches // repeats
    assert (seq_disp, comp_disp) == (2, 1), (seq_disp, comp_disp)

    # full-domain reference: ONE queue over the unsplit block (what the
    # linked rows must reproduce bit-for-bit modulo the documented
    # coalesced-dataflow FMA ULPs)
    fprog = build_faces_program(cfg, mesh).persistent(inner)
    engF = PersistentEngine(fprog, mode="dataflow", donate=True)
    freshF = lambda: engF.init_buffers({"u": u0})
    full_out = engF(freshF())
    full_u = np.asarray(full_out["u"])
    engF.stats.reset()
    full = _time_engine(engF, None, 1, repeats, fresh=freshF)
    full_disp = engF.stats.dispatches // repeats

    # linked N-way: cross-program channels carry the shared faces (and
    # the stencil's ghost planes), one dispatch for the REAL solve.
    # Each part count gets TWO rows: `_untuned` pins the default knobs
    # (round-robin interleave, dataflow) as the regression reference,
    # and the published linked row is what the generic auto-tuner
    # (repro.launch.tune) picks over interleave policy × trigger mode.
    from repro.launch.tune import Knobs, tune as tune_search

    rows = [("sequential_2q", seq, seq_disp),
            ("composed_1q", comp, comp_disp),
            ("full_domain_1q", full, full_disp)]
    for n_parts in (2, 4):
        names = part_names(n_parts)
        progs = [build_faces_part_program(cfg, mesh, k, n_parts,
                                          names=names).persistent(inner)
                 for k in range(n_parts)]
        parts = split_parts(u0, n_parts)

        def mk_fresh(eng, nm=names, p=parts):
            return lambda: eng.init_buffers(
                {f"{n}/u": x for n, x in zip(nm, p)})

        engL = PersistentEngine(compose(*progs), mode="dataflow",
                                donate=True)
        freshL = mk_fresh(engL)
        warmL = engL(freshL())
        got = np.asarray(merge_parts([warmL[f"{n}/u"] for n in names]))
        np.testing.assert_allclose(got, full_u, rtol=1e-5, atol=1e-6)
        engL.stats.reset()
        linked = _time_engine(engL, None, 1, repeats, fresh=freshL)
        linked_disp = engL.stats.dispatches // repeats
        assert linked_disp == 1, linked_disp
        rows.append((f"linked_1q_n{n_parts}_untuned", linked, linked_disp))

        def build(knobs, progs=progs, mk=mk_fresh):
            eng = PersistentEngine(
                compose(*progs, interleave=knobs.interleave_policy()),
                mode=knobs.mode, donate=True)
            return eng, mk(eng)

        def check_solve(cand, nm=names):
            w = cand.engine(cand.fresh())
            got = np.asarray(merge_parts([w[f"{n}/u"] for n in nm]))
            np.testing.assert_allclose(got, full_u, rtol=1e-5, atol=1e-6)

        res = tune_search(build,
                          {"interleave": ["round_robin", "sequential", 2],
                           "mode": ["dataflow", "stream"]},
                          inner=1, repeats=repeats, measure_top=2,
                          certify=True, check=check_solve)
        engT, freshT = res.best.engine, res.best.fresh
        cert = res.best.certificate
        if cert is None or not cert.equivalent:
            # no effect-trace proof: fall back to the numeric check
            # (the tuner already warmed the winner, so this is the
            # only extra solve we pay)
            warmT = engT(freshT())
            gotT = np.asarray(merge_parts([warmT[f"{n}/u"] for n in names]))
            np.testing.assert_allclose(gotT, full_u, rtol=1e-5, atol=1e-6)
        # publish an apples-to-apples number: re-measure the winner
        # back-to-back with the untuned reference above (the tuner's own
        # medians come from a different cache/compile context), and if
        # the head-to-head says the default wins, the tuned choice IS
        # the default — the published row must never be the slower one.
        engT.stats.reset()
        tuned_meas = _time_engine(engT, None, 1, repeats, fresh=freshT)
        assert engT.stats.dispatches // repeats == 1, engT.stats.dispatches
        if tuned_meas["med_s"] <= linked["med_s"]:
            knobs = res.knobs_dict()
            tuned = dict(tuned_meas, note="knobs=" + res.best.knobs.label())
        else:
            knobs = Knobs().asdict()
            tuned = dict(linked, note="knobs=default_fallback")
        TUNED_KNOBS[f"faces_pipeline/linked_1q_n{n_parts}"] = knobs
        rows.append((f"linked_1q_n{n_parts}", tuned, 1))

    speedup = seq["avg_s"] / comp["avg_s"] if comp["avg_s"] else float("nan")
    linked2 = next(r for n, r, _ in rows if n == "linked_1q_n2")
    linked_speedup = (full["avg_s"] / linked2["avg_s"]
                      if linked2["avg_s"] else float("nan"))
    for name, r, disp in rows:
        derived = (f"dispatches_per_loop={disp};"
                   f"overlap_speedup={speedup:.3f};"
                   f"linked_vs_full={linked_speedup:.3f}")
        if r.get("note"):
            derived += f";{r['note']}"
        RESULTS.append({
            "bench": "faces_pipeline", "variant": name,
            "us_per_call": r["avg_s"] * 1e6,
            "median_ms": r["med_s"] * 1e3,
            "dispatches": disp,
            "derived": derived,
        })
        print(f"  pipe   {name:15s} avg={r['avg_s']*1e3:9.2f}ms "
              f"med={r['med_s']*1e3:9.2f}ms dispatch/loop={disp}")
    print(f"  overlap speedup (sequential/composed): {speedup:.3f}x "
          f"({inner} iterations, 2 half-grid queues); linked full-domain "
          f"solve vs single queue: {linked_speedup:.3f}x")
    return {"sequential_2q": seq, "composed_1q": comp, "full_domain_1q": full,
            "speedup": speedup, "linked_vs_full": linked_speedup}


def run_all():
    print("Faces microbenchmark (paper §V; 8 host devices)")
    for fn in (fig8, fig9, fig10, fig11, fig12, fig_persistent,
               fig_convergence, fig_pipeline):
        print(f"-- {fn.__name__}: {fn.__doc__.splitlines()[0]}")
        fn()
    return RESULTS
