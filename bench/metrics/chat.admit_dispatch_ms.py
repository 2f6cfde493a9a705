"""Device time of one execution of the admission program (ms, mean):
the trace's executions of the XLA module of ``_admit_decode_fn``."""

from bench import trace as tr

MODULE = r"^jit__admit_decode_fn\b"


def read(run):
    runs = tr.module_executions(run.trace, MODULE)
    return sum(runs) / len(runs) / 1e6 if runs else None
