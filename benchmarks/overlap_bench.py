"""Decomposed-collective + ST collective-matmul benchmarks.

Two sections:

**Decomposed overlap (8-device ring, fixed shapes).**  Contrasts stock
``all_gather``-then-matmul vs the per-chunk ``all_gather_matmul``
interleave, matmul-then-``psum_scatter`` vs ``matmul_reduce_scatter``,
and uni- vs bidirectional ring gathers — dispatch/fusion effects on
CPU, with the HLO collective-op count + wire bytes the TPU roofline
cares about in the derived column.

**Transformer block as ST schedule (the PR-9 headline).**  The same
collectives expressed as first-class ST descriptors
(:mod:`repro.core.collectives`): single-dispatch ``st_ag_matmul`` /
``st_matmul_rs`` / ``st_a2a`` rows assert bit-identity against the
decomposed references, and the gate rows run an N-layer Megatron-MLP
chain two ways —

``tp_stock_chain``     N jitted stock ``shard_map`` calls
                       (``psum_scatter(relu(all_gather(x)@w1)@w2)``),
                       one host dispatch per layer;
``tp_st_persistent``   the SAME chain as ONE
                       :class:`~repro.core.engine_persistent
                       .PersistentEngine` dispatch (``chain=True``
                       feedback kernel + ``program.persistent(N)``),
                       knobs picked by :func:`repro.launch.tune.tune`.

Emits ``BENCH_overlap.json`` (via ``benchmarks/run.py overlap``) with a
``_meta`` workload stamp; ``--check-against BENCH_overlap.json`` gates
CI:

* unconditional same-run invariants: the tuned ST chain **beats the
  stock shard_map chain** (measured back-to-back, machine speed cancels
  out), the tuner never publishes a slower number than untuned, and the
  ST rows really run in one dispatch;
* stored-median comparison (speed-factor-normalized) only when
  ``_meta`` matches, with tolerance widened by ``BENCH_NOISE_FACTOR``
  (``--noise-factor`` in run.py) for noisy 1-core runners.

Env knobs: OVERLAP_DEVICES, OVERLAP_M, OVERLAP_K, OVERLAP_F,
OVERLAP_LAYERS, OVERLAP_REPEATS.  The defaults (4-device ring,
m=512/k=128/f=128, 16 layers) are the collective-bound regime where the
ring cost is small enough per layer that dispatch amortization wins.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial
from typing import Dict, List

import numpy as np

RESULTS: List[Dict] = []
# tuner-chosen knobs per published row — stamped into _meta by collect()
TUNED_KNOBS: Dict[str, Dict] = {}

CHECK_TOLERANCE = 1.20


def _noise_factor() -> float:
    """Explicit gate-tolerance widening for noisy 1-core CI runners
    (``--noise-factor`` in run.py sets BENCH_NOISE_FACTOR).  Never
    narrows below 1.0: the recorded medians stay the pin."""
    return max(1.0, float(os.environ.get("BENCH_NOISE_FACTOR", "1")))


def _cfg_env(name, default, cast=int):
    return cast(os.environ.get(name, default))


def _workload() -> Dict:
    return {
        "devices": _cfg_env("OVERLAP_DEVICES", 4),
        "m": _cfg_env("OVERLAP_M", 512),
        "k": _cfg_env("OVERLAP_K", 128),
        "f": _cfg_env("OVERLAP_F", 128),
        "layers": _cfg_env("OVERLAP_LAYERS", 16),
        "repeats": _cfg_env("OVERLAP_REPEATS", 5),
    }


def _time(fn, *args, repeats=20):
    import jax
    fn(*args)  # warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6


def _run_decomposed():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import overlap
    from repro.launch.hlo_analysis import analyze_collectives
    from repro.parallel import make_mesh

    mesh = make_mesh((8,), ("x",))
    n = 8
    print("Decomposed/overlapped collectives (8-device ring)")

    def smap(f, in_specs, out_specs):
        return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    rng = np.random.RandomState(0)
    x = rng.randn(1024, 512).astype(np.float32)   # gathered over rows
    w = rng.randn(512, 256).astype(np.float32)

    cases = {
        "ag_then_matmul": smap(
            lambda a, b: jax.lax.all_gather(a, "x", axis=0, tiled=True) @ b,
            (P("x"), P()), P()),
        "ag_matmul_overlap": smap(
            partial(overlap.all_gather_matmul, axis="x"),
            (P("x"), P()), P()),
    }
    xk = rng.randn(1024, 512).astype(np.float32)
    wk = rng.randn(512, 256).astype(np.float32)
    cases["matmul_then_rs"] = smap(
        lambda a, b: jax.lax.psum_scatter(a @ b, "x", scatter_dimension=0,
                                          tiled=True),
        (P(None, "x"), P("x")), P("x"))
    cases["matmul_rs_overlap"] = smap(
        partial(overlap.matmul_reduce_scatter, axis="x"),
        (P(None, "x"), P("x")), P("x"))
    cases["ag_ring_uni"] = smap(
        partial(overlap.all_gather_ring, axis="x", bidirectional=False),
        (P("x"),), P())
    cases["ag_ring_bidi"] = smap(
        partial(overlap.all_gather_ring, axis="x", bidirectional=True),
        (P("x"),), P())

    for name, fn in cases.items():
        args = (x, w) if "matmul" in name and "rs" not in name else (
            (xk, wk) if "rs" in name else (x,))
        us = _time(fn, *args)
        lowered = fn.lower(*args)
        colls = analyze_collectives(lowered.compile().as_text(), n)
        derived = (f"coll_ops={sum(colls.count_by_kind.values())};"
                   f"wire_bytes={colls.total_bytes:.3e}")
        RESULTS.append({"bench": "overlap", "variant": name,
                        "us_per_call": us, "derived": derived})
        print(f"  {name:20s} {us:10.1f} us  {derived}")

    # serial-step count: bidi ring halves the chain depth
    RESULTS.append({
        "bench": "overlap", "variant": "ring_steps",
        "us_per_call": 0.0,
        "derived": f"uni_steps={n-1};bidi_steps={(n-1+1)//2}"})


def _run_st(w: Dict):
    """ST collective-matmul rows: bit-identity asserts + the tuned
    persistent transformer-block chain vs the stock shard_map chain."""
    import jax
    from repro.core import collectives
    from repro.core.engine_fused import FusedEngine
    from repro.core.engine_persistent import PersistentEngine
    from repro.launch.tune import Knobs, measure, tune
    from repro.parallel import make_mesh

    n = w["devices"]
    m, k, f, layers = w["m"], w["k"], w["f"], w["layers"]
    repeats = w["repeats"]
    mesh = make_mesh((n,), ("x",))
    rng = np.random.RandomState(0)
    print(f"\nST collective matmul ({n}-device ring, m={m} k={k} f={f}, "
          f"{layers}-layer chain)")

    def row(variant, median_ms, dispatches, derived):
        RESULTS.append({"bench": "overlap", "variant": variant,
                        "us_per_call": median_ms * 1e3,
                        "median_ms": median_ms, "dispatches": dispatches,
                        "derived": derived})
        print(f"  {variant:22s} {median_ms:9.2f} ms  "
              f"dispatches={dispatches:3d}  {derived}")

    # --- single-dispatch ST collectives: bit-identical, priced, timed
    builders = {
        "st_ag_matmul": (
            lambda: collectives.build_all_gather_matmul(mesh, "x", m, k, f),
            lambda: {"x": rng.randn(m, k).astype(np.float32),
                     "w": rng.randn(k, f).astype(np.float32)}),
        "st_matmul_rs": (
            lambda: collectives.build_matmul_reduce_scatter(
                mesh, "x", m, k, f),
            lambda: {"x": rng.randn(m, k).astype(np.float32),
                     "w": rng.randn(k, f).astype(np.float32)}),
        "st_a2a": (
            lambda: collectives.build_all_to_all(mesh, "x", m, k),
            lambda: {"x": rng.randn(m, k).astype(np.float32)}),
    }
    for variant, (build, make_in) in builders.items():
        cm = build()
        eng = FusedEngine(cm.program, mode="dataflow")
        inputs = make_in()
        mem = eng.init_buffers(inputs)
        out = np.asarray(eng(mem)[cm.output])
        ref = np.asarray(cm.reference(*(inputs[b] for b in cm.inputs)))
        bitwise = bool((out == ref).all())
        assert bitwise, f"{variant}: ST output != decomposed reference"
        st = measure(eng, lambda: eng.init_buffers(make_in()), 1, repeats)
        ref_t = measure(lambda a: cm.reference(*a),
                        lambda: tuple(inputs[b] for b in cm.inputs),
                        1, repeats)
        row(variant, st["med_s"] * 1e3, 1,
            f"bitwise_vs_decomposed={bitwise};"
            f"reference_ms={ref_t['med_s'] * 1e3:.2f}")

    # --- the gate: N-layer TP-MLP chain, stock vs persistent ST
    tp = collectives.build_tp_block(mesh, "x", m, k, f, chain=True)
    x0 = rng.randn(m, k).astype(np.float32)
    w1 = rng.randn(k, f).astype(np.float32)
    w2 = rng.randn(f, k).astype(np.float32)
    stock = tp.reference_stock

    def stock_chain(a):
        for _ in range(layers):
            a = stock(a, w1, w2)
        return a

    st_stock = measure(stock_chain, lambda: x0, 1, repeats)
    row("tp_stock_chain", st_stock["med_s"] * 1e3, layers,
        f"layers={layers};lowering=shard_map")

    pprog = tp.program.persistent(layers)

    def fresh():
        # donate=True consumes the carry: re-materialize per repeat
        return PersistentEngine(pprog, donate=True).init_buffers(
            {"x": x0, "w1": w1, "w2": w2})

    def build(knobs: Knobs):
        eng = PersistentEngine(pprog, donate=True, **knobs.engine_kwargs())
        return eng, lambda: eng.init_buffers({"x": x0, "w1": w1, "w2": w2})

    # bit-identity of the whole chain: persistent(N) == N decomposed
    # block applications (the feedback kernel feeds out back into x)
    eng0, fresh0 = build(Knobs())
    chained = np.asarray(eng0(fresh0())["out"])
    ref = x0
    for _ in range(layers):
        ref = tp.reference(ref, w1, w2)
    assert (chained == np.asarray(ref)).all(), \
        "persistent ST chain != decomposed reference chain"
    st_untuned = measure(eng0, fresh0, 1, repeats)
    row("tp_st_persistent_untuned", st_untuned["med_s"] * 1e3, 1,
        f"layers={layers};knobs=default")

    res = tune(build,
               {"mode": ["stream", "dataflow"],
                "coalesce": [True, False],
                "double_buffer": [None, False]},
               inner=1, repeats=repeats, measure_top=3)
    # the default point (= the untuned row, already measured with the
    # same loop) is part of the space: publish whichever measured
    # faster, with the knobs that produced the published number
    best_ms, best_knobs = res.best.measured_ms, res.best.knobs
    if st_untuned["med_s"] * 1e3 < best_ms:
        best_ms, best_knobs = st_untuned["med_s"] * 1e3, Knobs()
    TUNED_KNOBS["overlap/tp_st_persistent"] = best_knobs.asdict()
    row("tp_st_persistent", best_ms, 1,
        f"layers={layers};knobs={best_knobs.label()};"
        f"speedup_vs_stock={st_stock['med_s'] * 1e3 / best_ms:.2f}x")


def run_all():
    _run_decomposed()
    _run_st(_workload())
    return RESULTS


def collect(results: List[Dict]) -> Dict:
    """BENCH_overlap.json payload from run_all() rows (rows without a
    median — the legacy us_per_call section — are not tracked)."""
    out = {
        f"{r['bench']}/{r['variant']}": {
            "median_ms": round(r["median_ms"], 4),
            "dispatches": r["dispatches"],
        }
        for r in results
        if r["bench"] == "overlap" and "median_ms" in r
    }
    if out:
        w = _workload()
        out["_meta"] = {k: w[k] for k in
                        ("devices", "m", "k", "f", "layers", "repeats")}
        if TUNED_KNOBS:
            out["_meta"]["tuned_knobs"] = TUNED_KNOBS
    return out


def check_against(fresh: Dict, path: str) -> int:
    """Overlap perf gate (cf. the Faces gate in benchmarks/run.py).

    Same-run invariants are unconditional — the variants are measured
    back-to-back in one process, so machine speed cancels out:

    * the tuned persistent ST chain beats the stock shard_map chain
      (the PR-9 acceptance criterion: model parallelism through the ST
      scheduler must win on a collective-bound shape);
    * the auto-tuner never publishes a slower number than untuned;
    * the ST rows really run in ONE dispatch.

    Stored medians are only compared when the ``_meta`` workload stamp
    (minus the advisory ``tuned_knobs``) matches, normalized by the
    run-wide speed factor, with tolerance widened by BENCH_NOISE_FACTOR
    for noisy runners.  Knob drift is a warning, never a failure.
    """
    with open(path) as f:
        stored = json.load(f)

    failures = []
    st = fresh.get("overlap/tp_st_persistent")
    stock = fresh.get("overlap/tp_stock_chain")
    untuned = fresh.get("overlap/tp_st_persistent_untuned")
    if st and stock and st["median_ms"] >= stock["median_ms"]:
        failures.append(
            f"overlap/tp_st_persistent ({st['median_ms']:.2f}ms) does not "
            f"beat overlap/tp_stock_chain ({stock['median_ms']:.2f}ms): "
            f"the tuned ST transformer-block chain must beat the stock "
            f"shard_map lowering")
    if st and untuned and st["median_ms"] > untuned["median_ms"] * 1.05:
        failures.append(
            f"overlap/tp_st_persistent ({st['median_ms']:.2f}ms) is slower "
            f"than untuned ({untuned['median_ms']:.2f}ms): the auto-tuner "
            f"must never publish a slower number")
    for key in ("overlap/st_ag_matmul", "overlap/st_matmul_rs",
                "overlap/st_a2a", "overlap/tp_st_persistent"):
        r = fresh.get(key)
        if r and r.get("dispatches") != 1:
            failures.append(
                f"{key} used {r.get('dispatches')} dispatches: ST "
                f"collective-matmul rows must run in one dispatch")

    stored_meta = stored.get("_meta", {})
    fresh_meta = fresh.get("_meta", {})
    stored_knobs = stored_meta.get("tuned_knobs", {})
    fresh_knobs = fresh_meta.get("tuned_knobs", {})
    stored_settings = {kk: v for kk, v in stored_meta.items()
                       if kk != "tuned_knobs"}
    fresh_settings = {kk: v for kk, v in fresh_meta.items()
                      if kk != "tuned_knobs"}
    if not stored_settings:
        print("note: recorded file has no _meta stamp — median checks "
              "skipped (invariants only)")
        compare = False
    elif stored_settings != fresh_settings:
        print(f"note: workload differs from recorded ({fresh_settings} vs "
              f"{stored_settings}) — median checks skipped, invariants "
              f"enforced")
        compare = False
    else:
        compare = True
    if compare and stored_knobs:
        for rr in sorted(set(stored_knobs) | set(fresh_knobs)):
            if stored_knobs.get(rr) != fresh_knobs.get(rr):
                print(f"WARNING knob-drift {rr}: recorded "
                      f"{stored_knobs.get(rr)} vs re-tuned "
                      f"{fresh_knobs.get(rr)} — a re-tune now picks "
                      f"differently; re-record {path} to pin the new choice")

    if compare:
        tol = CHECK_TOLERANCE * _noise_factor()
        keys = [kk for kk in fresh if not kk.startswith("_")
                and isinstance(stored.get(kk), dict)
                and stored[kk].get("median_ms")]
        ratios = sorted(fresh[kk]["median_ms"] / stored[kk]["median_ms"]
                        for kk in keys)
        speed = ratios[len(ratios) // 2] if ratios else 1.0
        for kk in keys:
            bound = stored[kk]["median_ms"] * speed * tol
            if fresh[kk]["median_ms"] > bound:
                failures.append(
                    f"{kk}: median {fresh[kk]['median_ms']:.2f}ms > bound "
                    f"{bound:.2f}ms (recorded "
                    f"{stored[kk]['median_ms']:.2f}ms x speed {speed:.2f} "
                    f"x tolerance {tol:.2f})")

    if failures:
        # stderr + flush, mirroring the Faces/serve gates: the non-zero
        # exit must name every failing row in the CI log
        print(f"\nOVERLAP PERF GATE FAILED ({len(failures)} failing "
              f"row(s)):", file=sys.stderr, flush=True)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr, flush=True)
        names = ", ".join(msg.split(":", 1)[0] for msg in failures)
        print(f"OVERLAP PERF GATE FAILED rows: {names}", file=sys.stderr,
              flush=True)
        return 1
    print("\noverlap perf gate OK: tuned ST chain beats stock shard_map "
          "chain; tuned <= untuned; ST rows are 1-dispatch"
          + ("; medians within tolerance" if compare else ""))
    return 0
