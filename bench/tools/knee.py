#!/usr/bin/env python3
"""Sweep the arrival rate of a serving cell to find its knee, on the chip.

    python3 bench/tools/knee.py --workload qwen1.5-0.5b.chat-poisson \
        --rates 4 8 12 16 --seconds 20

One process sets the cell up once, then serves the cell's mix at each
rate for ``--seconds`` (``round(rate * seconds)`` requests).  For each
rate it prints the queue of requests waiting for a slot, sampled at
every admission, as its mean over the first and the last third of the
arrivals, with the latency tail and the tokens per second.  The knee is
the highest rate at which the queue does not grow over the window.
A rate given more than once is served again: the spread of its tail
between repeats shows how steady the tail is at that load, and
``slot_wait_share`` how many requests waited for a free slot, and
``stalls`` the dispatches that took far longer than others of their kind.
"""

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def backlog(d):
    """Waiting requests at each admission: arrived minus admitted."""
    from bench.drivers.serve_continuous import dispatch_work

    admitted, out = 0, []
    for c in dispatch_work(d.calls, d.P, d.slots):
        if c["kind"] != "admit":
            continue
        t = c["t0"] - d.t_origin
        arrived = int(np.searchsorted(d.offsets, t, side="right"))
        out.append((t, arrived - admitted))
        admitted += c["admitted"]
    return out


def slot_waits(d):
    """Share of requests not admitted at the first dispatch made after
    they were due: those that waited for a free slot."""
    from bench.drivers.serve_continuous import dispatch_work

    calls = dispatch_work(d.calls, d.P, d.slots)
    t0 = np.asarray([c["t0"] - d.t_origin for c in calls])
    admitted = np.cumsum([c["admitted"] for c in calls])
    at = np.searchsorted(admitted, np.arange(d.n_requests), side="right")
    first = np.searchsorted(t0, d.offsets, side="left")
    return float(np.mean(at > first))


def round_ms(d):
    """Host time (ms) of each dispatch, by the engine callable made."""
    out = {}
    for c in d.calls:
        out.setdefault(c.name, []).append((c.t_ready - c.t0) * 1e3)
    return out


def stalls(rounds, over_ms=30.0):
    """Dispatches that took ``over_ms`` longer than the median of their
    kind, as (kind, ms over the median)."""
    return [(k, round(x - float(np.median(v)), 1))
            for k, v in rounds.items() for x in v
            if x - np.median(v) > over_ms]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.tools.limits import _driver

    harness.enable_compile_cache(ROOT)
    _, d = _driver(args.workload, args.seed, args.seconds)
    d.setup()
    for rate in args.rates:
        d.rate = rate
        d.n_requests = max(1, round(rate * args.seconds))
        d.reseed(args.seed)
        d.window(None)
        q = backlog(d)
        rounds = round_ms(d)
        span = d.offsets[-1]
        first = [b for t, b in q if t <= span / 3]
        last = [b for t, b in q if 2 * span / 3 <= t <= span]
        lat = (d.done - d.due) * 1e3
        print(json.dumps({
            "rate": rate, "requests": d.n_requests,
            "queue_first_third": float(np.mean(first)) if first else None,
            "queue_last_third": float(np.mean(last)) if last else None,
            "queue_max": max(b for _, b in q),
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "latency_p90_p99_ms": [float(np.percentile(lat, pct))
                                   for pct in (90, 99)],
            "slot_wait_share": slot_waits(d),
            "round_ms": {k: [float(np.mean(v)), len(v)]
                         for k, v in rounds.items()},
            "stalls": stalls(rounds),
            "tok_per_s": (sum(map(len, d.tokens.values()))
                          / (d.t_end - d.t_start)),
            "serve_s": d.t_end - d.t_start}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
