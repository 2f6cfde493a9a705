import os
# Benchmarks need a small multi-device grid (the Faces figures use 8
# ranks, matching the paper's 8-node experiments).  This is the bench
# entry point only — tests and the dry-run manage their own device
# counts (dryrun.py forces 512; pytest keeps the 1 real device).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Benchmark harness — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run faces      # one suite

Prints ``name,us_per_call,derived`` CSV at the end (plus human-readable
sections), and writes artifacts/bench_results.json.

Perf-regression gate::

  PYTHONPATH=src python -m benchmarks.run faces --check-against BENCH_faces.json

re-measures and exits non-zero if (a) any tracked Faces variant's
median regressed more than 20% vs the recorded file after normalizing
by the run-wide speed factor (machines differ; one variant drifting
beyond the rest of its own run is what counts), or (b) the
device-resident ``faces_figP/persistent`` loop measures slower than
re-dispatching ``fused_per_iter`` — the contract this repo's headline
depends on.  In gate mode BENCH_faces.json is *not* rewritten (CI must
not publish the numbers it is judging).

The serving and overlap suites have their own files and gates (see
benchmarks/serve_bench.py, benchmarks/overlap_bench.py)::

  PYTHONPATH=src python -m benchmarks.run serve --check-against BENCH_serve.json
  PYTHONPATH=src python -m benchmarks.run overlap --check-against BENCH_overlap.json

``--noise-factor F`` (or env BENCH_NOISE_FACTOR) widens every gate's
median tolerance by F for noisy 1-core runners; the same-run invariants
are never relaxed.
"""

import json
import sys

# medians on the CPU grid jitter run-to-run; >20% is a regression, not noise
CHECK_TOLERANCE = 1.20


def _noise_factor() -> float:
    """Explicit median-tolerance widening for noisy runners (set by
    ``--noise-factor`` / BENCH_NOISE_FACTOR); clamped at >= 1.0 so it
    can only relax, never tighten, the recorded pin."""
    return max(1.0, float(os.environ.get("BENCH_NOISE_FACTOR", "1")))


def check_against(faces: dict, path: str) -> int:
    """Compare fresh Faces medians to a recorded BENCH_faces.json.

    The comparison is normalized by the run-wide speed factor — the
    median of fresh/stored ratios across all tracked variants — so a
    uniformly slower/faster machine does not read as a regression, a
    single variant drifting >20% beyond the rest of the run does, and
    one variant *improving* cannot fail its unchanged siblings (with
    ~24 tracked variants the median barely moves).  Cross-run medians
    are only compared when the run's loop settings (``_meta``) match
    the recorded file's, and they assume a reasonably quiet machine
    (host-dispatch-bound baselines are very sensitive to CPU
    contention).  The same-run invariants — persistent beats
    per-iteration re-dispatch, the auto-tuner never publishes a slower
    number — are enforced unconditionally; they are what CI's
    small-grid run gates (its settings never match the recorded file,
    so the median path never runs there).
    """
    with open(path) as f:
        stored = json.load(f)

    # per-variant median comparison is only meaningful when this run
    # used the same loop settings the file was recorded with (a smaller
    # FACES_INNER rescales host-dispatch-bound and fused variants
    # differently); otherwise only the absolute invariants below apply.
    # A file WITHOUT a _meta stamp never gets median-compared either —
    # its loop settings are unknown, so a stale file could fail CI
    # spuriously (or pass wrongly) at arbitrary mismatched settings.
    stored_meta = stored.get("_meta", {})
    fresh_meta = faces.get("_meta", {})
    # the tuner's chosen knobs ride in _meta but are advisory: only the
    # LOOP settings decide whether medians are comparable.  Knob drift
    # (a re-tune at like-for-like settings now picks differently) is a
    # warning row, never a failure — the recorded file stays the pin
    # until someone re-records it.
    stored_knobs = stored_meta.get("tuned_knobs", {})
    fresh_knobs = fresh_meta.get("tuned_knobs", {})
    stored_settings = {k: v for k, v in stored_meta.items()
                       if k != "tuned_knobs"}
    fresh_settings = {k: v for k, v in fresh_meta.items()
                      if k != "tuned_knobs"}
    if not stored_settings:
        compare_medians = False
        print("note: recorded file has no _meta settings stamp — median "
              "checks skipped (invariants only); re-record it to enable them")
    elif stored_settings != fresh_settings:
        compare_medians = False
        print(f"note: settings differ from recorded ({fresh_settings} vs "
              f"{stored_settings}) — median checks skipped, invariants "
              f"enforced")
    else:
        compare_medians = True
    if compare_medians and stored_knobs:
        for row in sorted(set(stored_knobs) | set(fresh_knobs)):
            if stored_knobs.get(row) != fresh_knobs.get(row):
                print(f"WARNING knob-drift {row}: recorded "
                      f"{stored_knobs.get(row)} vs re-tuned "
                      f"{fresh_knobs.get(row)} — a re-tune now picks "
                      f"differently; re-record {path} to pin the new choice")

    def tracked(key):
        f, s = faces.get(key), stored.get(key)
        return (isinstance(f, dict) and f.get("median_ms")
                and isinstance(s, dict) and s.get("median_ms"))

    ratios = sorted(faces[k]["median_ms"] / stored[k]["median_ms"]
                    for k in faces if compare_medians and tracked(k))
    speed = ratios[len(ratios) // 2] if ratios else 1.0
    failures = []
    tol = CHECK_TOLERANCE * _noise_factor()
    if compare_medians:
        for key, fresh in sorted(faces.items()):
            if not tracked(key):
                continue
            bound = stored[key]["median_ms"] * speed * tol
            if fresh["median_ms"] > bound:
                failures.append(
                    f"{key}: median {fresh['median_ms']:.1f}ms > bound "
                    f"{bound:.1f}ms (recorded "
                    f"{stored[key]['median_ms']:.1f}ms x run speed-factor "
                    f"{speed:.2f} x tolerance {tol:.2f}: "
                    f">{(tol-1)*100:.0f}% regression)")
    # absolute same-run invariants: these pairs are measured back-to-back
    # in one process, so machine speed and loop settings cancel out
    pers = faces.get("faces_figP/persistent")
    fused = faces.get("faces_figP/fused_per_iter")
    if pers and fused and pers["median_ms"] > fused["median_ms"]:
        failures.append(
            f"faces_figP/persistent ({pers['median_ms']:.1f}ms) is slower "
            f"than fused_per_iter ({fused['median_ms']:.1f}ms): the "
            f"1-dispatch path must also be the fastest path")
    tuned = faces.get("faces_fig12/st_tuned")
    offl = faces.get("faces_fig12/st_offload")
    if tuned and offl and tuned["median_ms"] > offl["median_ms"] * 1.05:
        failures.append(
            f"faces_fig12/st_tuned ({tuned['median_ms']:.1f}ms) is slower "
            f"than untuned st_offload ({offl['median_ms']:.1f}ms): the "
            f"auto-tuner must never publish a slower number")
    for n in (2, 4):
        t = faces.get(f"faces_pipeline/linked_1q_n{n}")
        u = faces.get(f"faces_pipeline/linked_1q_n{n}_untuned")
        if t and u and t["median_ms"] > u["median_ms"] * 1.05:
            failures.append(
                f"faces_pipeline/linked_1q_n{n} ({t['median_ms']:.1f}ms) is "
                f"slower than its untuned reference "
                f"({u['median_ms']:.1f}ms): the auto-tuner must never "
                f"publish a slower linked row")
    if failures:
        # stderr + flush: the non-zero exit must never be near-silent in
        # CI logs — name every failing row, then a one-line summary
        print(f"\nPERF GATE FAILED ({len(failures)} failing row(s)):",
              file=sys.stderr, flush=True)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr, flush=True)
        names = ", ".join(msg.split(":", 1)[0] for msg in failures)
        print(f"PERF GATE FAILED rows: {names}", file=sys.stderr, flush=True)
        return 1
    checked = sum(1 for k in faces if tracked(k)) if compare_medians else 0
    print(f"\nperf gate OK: {checked} tracked medians within "
          f"{(tol-1)*100:.0f}% of {path} "
          f"(speed-normalized x{speed:.2f}); invariants hold "
          f"(persistent <= fused, tuned <= offload, "
          f"tuned linked <= untuned)")
    return 0


def main() -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "..", "src"))
    sys.path.insert(0, os.path.join(here, ".."))

    from benchmarks import api_overhead, faces_bench, overlap_bench, \
        serve_bench
    from benchmarks import roofline as roofline_mod
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    argv = sys.argv[1:]
    check_path = None
    if "--check-against" in argv:
        i = argv.index("--check-against")
        check_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if "--noise-factor" in argv:
        i = argv.index("--noise-factor")
        os.environ["BENCH_NOISE_FACTOR"] = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    which = argv[0] if argv else "all"
    results = []

    if which in ("all", "api"):
        results += api_overhead.run_all()
    if which in ("all", "faces"):
        results += faces_bench.run_all()
    if which in ("all", "overlap"):
        results += overlap_bench.run_all()
    if which in ("all", "serve"):
        results += serve_bench.run_all()
    if which in ("all", "roofline"):
        rows = roofline_mod.main(None)
        for r in rows:
            if "skipped" in r:
                continue
            if "st_program" in r:  # cost-model rows carry their own shape
                meas = r.get("measured_ms")
                results.append({
                    "bench": "roofline_st", "variant": r["st_program"],
                    "us_per_call": r["predicted_us"],
                    "derived": f"predicted_us={r['predicted_us']:.0f};"
                               f"measured_ms="
                               f"{'-' if meas is None else f'{meas:.2f}'};"
                               f"bench_row={r['bench_row']}",
                })
                continue
            results.append({
                "bench": "roofline", "variant": f"{r['arch']}/{r['shape']}",
                "us_per_call": max(r["t_compute_s"], r["t_memory_s"],
                                   r["t_collective_s"]) * 1e6,
                "derived": f"dominant={r['dominant']};"
                           f"useful={r['useful_ratio']:.3f}",
            })

    print("\nname,us_per_call,derived")
    for r in results:
        print(f"{r['bench']}/{r['variant']},{r['us_per_call']:.2f},"
              f"\"{r['derived']}\"")

    out = os.path.join(here, "..", "artifacts", "bench_results.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"\nwrote {out}")

    # machine-readable Faces perf trajectory (variant -> median ms,
    # dispatch counts), tracked across PRs at the repo root
    faces = {
        f"{r['bench']}/{r['variant']}": {
            "median_ms": round(r["median_ms"], 4),
            "dispatches": r["dispatches"],
        }
        for r in results
        if r["bench"].startswith("faces") and "median_ms" in r
    }
    if faces:
        # loop settings stamp: median checks only compare like-for-like
        faces["_meta"] = {
            "faces_inner": int(os.environ.get("FACES_INNER", 10)),
            "faces_max_iters": int(os.environ.get("FACES_MAX_ITERS", 64)),
        }
        if faces_bench.TUNED_KNOBS:
            # tuner-chosen knobs per published row: pinned by the gate's
            # knob-drift warning above
            faces["_meta"]["tuned_knobs"] = faces_bench.TUNED_KNOBS
    # machine-readable serve + overlap trajectories (medians, dispatch
    # counts), tracked at the repo root like BENCH_faces.json
    serve = serve_bench.collect(results)
    ovl = overlap_bench.collect(results)

    if check_path is not None:
        # the gate matching the suite that ran: `serve`/`overlap`
        # --check-against judge their own file's invariants/medians,
        # every other selection keeps judging the Faces file
        if which == "serve":
            sys.exit(serve_bench.check_against(serve, check_path))
        if which == "overlap":
            sys.exit(overlap_bench.check_against(ovl, check_path))
        sys.exit(check_against(faces, check_path))
    if faces:
        fout = os.path.join(here, "..", "BENCH_faces.json")
        with open(fout, "w") as f:
            json.dump(faces, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {fout}")
    if serve:
        fout = os.path.join(here, "..", "BENCH_serve.json")
        with open(fout, "w") as f:
            json.dump(serve, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {fout}")
    if ovl:
        fout = os.path.join(here, "..", "BENCH_overlap.json")
        with open(fout, "w") as f:
            json.dump(ovl, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {fout}")


if __name__ == '__main__':
    main()
