"""Host time to enqueue one persistent Faces dispatch (ms, mean): the
benchmark's host span around each ``PersistentEngine`` call."""


def read(run):
    spans = run.spans.get("bench.enqueue")
    return sum(spans) / len(spans) * 1e3 if spans else None
