"""Nothing compiles inside the measured window, and the serving cell's
request latencies reach the per-layer reader of their tail."""

import logging
import tempfile

import jax
import numpy as np
import pytest

import tiny
from bench import harness


@pytest.fixture(scope="module")
def root():
    return tiny.make_root(tempfile.mkdtemp())


def _driver(root, workload):
    spec = harness.load_spec(root)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    traffic = harness.load_traffic(root, cell["traffic"])
    ctx = harness.Context(
        config=harness.load_config(root, spec, cell["config"]),
        params=traffic["params"], seed=2 ** 31 + 5, seconds=0.5,
        devices=jax.devices()[:1], chips=1)
    return harness.load_driver(root, traffic["driver"])(ctx)


class _Compiles(logging.Handler):
    def __init__(self):
        super().__init__()
        self.seen = []

    def emit(self, record):
        if record.getMessage().startswith("Compiling"):
            self.seen.append(record.getMessage()[:120])


@pytest.mark.parametrize("cell", ["faces", "serve"])
def test_window_compiles_nothing(root, cell):
    d = _driver(root, cell)
    d.setup()
    seen = _Compiles()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(seen)
    try:
        with jax.log_compiles(True):
            d.window(None)
    finally:
        logger.removeHandler(seen)
    assert seen.seen == []


class _Run:
    """What the reader sees of a traced run: requests' latencies and
    done times, and the traced part on the same host clock."""

    def __init__(self, latency, traced=None):
        self.counts = {"latency_ms": latency,
                       "done": list(range(len(latency)))} if latency else {}
        self.traced = traced


def test_latency_tail_reader(root):
    read = harness.load_reader(root, "chat.req_latency_p95_ms")
    first = list(np.arange(1.0, 51.0))
    assert read(_Run(first + first)) == pytest.approx(
        np.percentile(first + first, 95))
    # requests done once the profiler has started are left out
    assert read(_Run(first + [1e6] * 50, traced=(49.5, 60.0))) == \
        pytest.approx(np.percentile(first, 95))
    assert read(_Run([])) is None


def test_serving_run_counts_every_request_latency(root):
    d = _driver(root, "serve")
    d.setup()
    d.window(None)
    out = d.finish()
    assert len(out.counts["latency_ms"]) == d.n_requests
    assert len(out.counts["done"]) == d.n_requests
    assert all(x > 0 for x in out.counts["latency_ms"])
