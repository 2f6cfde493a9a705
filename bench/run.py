#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload faces-256.1x1 --seed 7 --seconds 20 --trace 0

Reads ``BENCHMARK.json`` at the root of the checkout, sets the cell up
(counted in ``setup_s``), measures for ``--seconds``, checks what the
timed path produced against the benchmark's own reference, and prints
one JSON line as the last line of standard output.  With ``--trace 0``
it reports the cell's end-to-end metrics; with ``--trace 1`` it runs the
window under the profiler and reports the per-layer metrics, the
device's busy time and a breakdown.  The numbers compared with the
reference, each beside its limit, are the last lines of standard error
and the last key of the result.

There is no CPU fallback: without an accelerator, with fewer chips than
the cell asks for, with a device the peak table does not know, or
outside a checkout that holds the program under test (``src/repro``),
it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"bench: no program under test at {src}/repro")
    sys.path[:0] = [ROOT, src]
    from bench import harness
    from bench.peaks import UnknownDevice, peaks_for

    spec = harness.load_spec(ROOT)
    cell = next((w for w in spec["workloads"] if w["name"] == args.workload),
                None)
    if cell is None:
        sys.exit(f"bench: no workload {args.workload!r} in BENCHMARK.json")

    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.exit("bench: JAX found no accelerator; there is no CPU fallback")
    if len(devices) < cell["chips"]:
        sys.exit(f"bench: {args.workload} needs {cell['chips']} chips, "
                 f"JAX found {len(devices)}")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        sys.exit(f"bench: {e}")
    print(f"compile cache: {harness.enable_compile_cache(ROOT)}",
          file=sys.stderr, flush=True)

    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), devices, t_start=T_START,
                            peaks=peaks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
