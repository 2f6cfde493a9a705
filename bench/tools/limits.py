#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/tools/limits.py --workload faces-256.1x1 --seeds 1 2 3 \
        --control-seeds 1 2 3 --seconds 2

For each seed, one process sets the cell up once and reads the numbers
that a run compares with the reference: from the program (its timed path
at the cell's size and load, for ``--seconds``), and for the control
seeds also from the control, the reference computed one precision lower
in the program's place (bfloat16 for the float32 Faces field; float8 for
a model served in bfloat16, judged at the served positions).  One JSON
line per seed and reading; the benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _driver(workload, seed, seconds):
    import jax

    from bench import harness

    spec = harness.load_spec(ROOT)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    traffic = harness.load_traffic(ROOT, cell["traffic"])
    ctx = harness.Context(
        config=harness.load_config(ROOT, spec, cell["config"]),
        params=traffic["params"], seed=seed, seconds=seconds,
        devices=jax.devices()[: cell["chips"]], chips=cell["chips"])
    return traffic["driver"], harness.load_driver(ROOT, traffic["driver"])(ctx)


def faces(d, seeds, control_seeds, emit):
    import jax.numpy as jnp
    import numpy as np

    for seed in seeds:
        d.reseed(seed)
        res = d._dispatch(None)
        field = np.asarray(d.mem["u"], np.float64)
        want_u, want_r = d.reference()
        f, r = d.errors(field, res, want_u, want_r)
        emit(seed=seed, reading="program", field_rel_err=f,
             resid_rel_err=float(np.max(r)), last_residual=float(want_r[-1]))
        if seed in control_seeds:
            cu, cr = d.reference(jnp.bfloat16)
            f, r = d.errors(cu, cr, want_u, want_r)
            emit(seed=seed, reading="control", field_rel_err=f,
                 resid_rel_err=float(np.max(r)))


def serving(d, seeds, control_seeds, emit):
    from bench.refs import qwen as ref

    for seed in seeds:
        d.reseed(seed)
        d.window(None)
        checked = d.sample()
        cfg = d.ctx.config
        gaps = [ref.served_gap(d.params, cfg, d.prompts[i], toks)
                for i, toks in checked]
        emit(seed=seed, reading="program", logit_gap=max(gaps), gaps=gaps,
             requests=d.n_requests,
             unfinished=d.n_requests - len(d.finished()))
        if seed in control_seeds:
            gaps = [ref.served_gap(d.params, cfg, d.prompts[i], toks,
                                   quant=True) for i, toks in checked]
            emit(seed=seed, reading="control", logit_gap=max(gaps), gaps=gaps)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    harness.enable_compile_cache(ROOT)
    t0 = time.perf_counter()
    kind, d = _driver(args.workload, args.seeds[0], args.seconds)
    d.setup()

    def emit(**kw):
        kw.update(workload=args.workload, t=round(time.perf_counter() - t0, 1))
        print(json.dumps(kw), flush=True)

    run = faces if kind == "faces_restart" else serving
    run(d, args.seeds, set(args.control_seeds), emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
