"""95th percentile of request latency (ms): from the time a request was
due to the host sync that returned its last token, over every request
that finished before the profiler started.  Starting and stopping the
profiler holds the serving loop (stopping it, for tens of seconds while
the trace is collected), which would count in every later request.

Host stalls of seconds, in about one run in five, move this tail far
more than the spread of other runs, so it stands here and not among the
end-to-end metrics."""

import numpy as np


def read(run):
    latency, done = run.counts.get("latency_ms"), run.counts.get("done")
    if not latency:
        return None
    start = run.traced[0] if run.traced else np.inf
    before = [x for x, t in zip(latency, done) if t < start]
    return float(np.percentile(before, 95)) if before else None
