"""Share of the HBM roofline over the traced part (%): the least bytes
of its decode steps and admissions (parameters as stored, valid KV read,
admitted prompts' KV written) at peak bandwidth, over device-busy time."""

from bench import counts
from bench import trace as tr


def read(run):
    busy = tr.busy_ns(run.trace) / 1e9
    calls = run.traced_calls()
    if busy <= 0 or not calls:
        return None
    least = counts.serve_min_bytes(run.config, calls,
                                   run.counts["prompt_len"])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / busy
