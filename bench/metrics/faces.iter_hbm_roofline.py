"""Share of the HBM roofline in a Faces iteration (%): the iteration's
least bytes at peak bandwidth, over the device-busy time per iteration in
the traced part."""

from bench import trace as tr


def read(run):
    busy = tr.busy_ns(run.trace) / 1e9
    iters = (run.traced_spans("bench.enqueue")
             * run.counts["iters_per_dispatch"])
    if busy <= 0 or not iters:
        return None
    least = run.counts["iter_min_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least * iters / busy
