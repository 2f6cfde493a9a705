"""The benchmark's harness: discovery by name, the run, the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* a configuration: the ``file`` of its ``configs`` entry (JSON);
* a traffic mix: ``bench/traffic/<traffic>.json``, which names its
  driver and holds the mix's parameters;
* a driver: ``bench/drivers/<driver>.py``, a class ``Driver`` with
  ``setup()``, ``window(spans)`` and ``finish()``; the window calls
  ``spans.boundary()`` before each dispatch;
* a per-layer metric: ``bench/metrics/<metric>.py``, a function
  ``read(run)`` that returns a number or ``None`` when it finds nothing.

A later cell, mix or metric is therefore new files plus new entries in
``BENCHMARK.json``; nothing here names one.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = ["command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"]
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class SpecError(ValueError):
    """BENCHMARK.json or a file it names breaks the benchmark's rules."""


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------


def bench_dir(root: str) -> str:
    return os.path.join(root, "bench")


def load_spec(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry(entries: Sequence[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(root: str, spec: dict, name: str) -> dict:
    entry = _entry(spec["configs"], name, "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_traffic(root: str, name: str) -> dict:
    path = os.path.join(bench_dir(root), "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise SpecError(f"no traffic file for mix {name!r} at {path}")
    with open(path) as f:
        traffic = json.load(f)
    if set(traffic) != {"driver", "params"}:
        raise SpecError(f"{path}: keys must be driver and params")
    return traffic


def _load_module(path: str, modname: str):
    if not os.path.exists(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(root: str, name: str):
    path = os.path.join(bench_dir(root), "drivers", f"{name}.py")
    return _load_module(path, f"bench_driver_{name}").Driver


def load_reader(root: str, metric: str):
    path = os.path.join(bench_dir(root), "metrics", f"{metric}.py")
    return _load_module(path, "bench_metric_" + re.sub(r"\W", "_", metric)).read


def cell_metrics(spec: dict, workload: str):
    """The end-to-end and per-layer metric entries that ``workload``
    reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if m["moves"] in e2e_names
             and ("workloads" not in m or workload in m["workloads"])]
    return e2e, layer


def validate(spec: dict, root: str) -> None:
    """Check ``spec`` against the benchmark's rules; raise SpecError."""
    def need(cond, msg):
        if not cond:
            raise SpecError(msg)

    need(list(spec) == TOP_KEYS, f"top-level keys must be {TOP_KEYS}")
    need(isinstance(spec["run_seconds"], int)
         and 1 <= spec["run_seconds"] <= 51, "run_seconds must be 1..51")
    seen = set()
    for kind, keys in (("configs", CONFIG_KEYS), ("workloads", WORKLOAD_KEYS),
                       ("end_to_end", E2E_KEYS), ("per_layer", LAYER_KEYS)):
        for e in spec[kind]:
            extra = set(e) - keys - ({"workloads"} if kind in
                                     ("end_to_end", "per_layer") else set())
            need(keys <= set(e) and not extra,
                 f"{kind} entry {e.get('name')!r}: keys must be {sorted(keys)}")
            need(bool(NAME.match(e["name"])), f"bad name {e['name']!r}")
            key = (kind if kind in ("configs", "workloads") else "metric",
                   e["name"])
            need(key not in seen, f"duplicate name {e['name']!r}")
            seen.add(key)
            for field in ("why", "layer", "source"):
                if field in e:
                    v = e[field]
                    need(isinstance(v, str) and 1 <= len(v) <= 200
                         and "\n" not in v and "\t" not in v,
                         f"{e['name']}: bad {field}")
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        need(os.path.exists(os.path.join(root, c["file"])),
             f"missing config file {c['file']}")
        need(all(NAME.match(k) for k in c["reduced"]), "bad reduced key")
        need(any(w["config"] == c["name"] for w in spec["workloads"]),
             f"config {c['name']} has no cell")
    pairs = set()
    for w in spec["workloads"]:
        need(w["config"] in configs, f"{w['name']}: unknown config")
        need(bool(NAME.match(w["traffic"])), f"{w['name']}: bad traffic")
        need(w["chips"] in (1, 4), f"{w['name']}: chips must be 1 or 4")
        need((w["config"], w["traffic"]) not in pairs,
             f"{w['name']}: config and traffic pair repeated")
        pairs.add((w["config"], w["traffic"]))
        load_traffic(root, w["traffic"])
    need(sum(w["chips"] == 4 for w in spec["workloads"])
         <= max(1, len(cells) // 2), "too many four-chip cells")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    need("setup_s" in e2e, "setup_s is required")
    for m in spec["end_to_end"] + spec["per_layer"]:
        need(bool(UNIT.match(m["unit"])), f"{m['name']}: bad unit")
        need(m["better"] in ("lower", "higher"), f"{m['name']}: bad better")
        for w in m.get("workloads", []):
            need(w in cells, f"{m['name']}: unknown workload {w}")
    for m in spec["end_to_end"]:
        need(m["source"] in SOURCES_E2E, f"{m['name']}: bad source")
        need(0 < m["bound"] <= 0.25, f"{m['name']}: bound out of range")
    for m in spec["per_layer"]:
        need(m["source"] in SOURCES, f"{m['name']}: bad source")
        need(m["moves"] in e2e, f"{m['name']}: moves unknown metric")
        mover = e2e[m["moves"]]
        for w in m.get("workloads", []):
            need("workloads" not in mover or w in mover["workloads"],
                 f"{m['name']}: {w} does not report {m['moves']}")
        need(os.path.exists(os.path.join(bench_dir(root), "metrics",
                                         m["name"] + ".py")),
             f"{m['name']}: no reader")
    for w in cells:
        e2e_w, layer_w = cell_metrics(spec, w)
        need(len(e2e_w) >= 2 and layer_w, f"{w}: too few metrics")


# ---------------------------------------------------------------------------
# what a driver gets and gives
# ---------------------------------------------------------------------------


def seed32(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for JAX's key or NumPy's legacy RNG, drawn from the
    run's seed (which may exceed 32 bits)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(1)
    return int(words[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Context:
    config: dict
    params: dict
    seed: int
    seconds: float
    devices: list
    chips: int


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float
    ok: bool

    def line(self) -> str:
        return (f"check {self.name} = {self.value!r} (limit {self.limit!r}): "
                f"{'ok' if self.ok else 'FAIL'}")


@contextlib.contextmanager
def phase(name: str):
    """Log how long a part of the set-up took, on standard error."""
    t0 = time.perf_counter()
    yield
    print(f"set-up {name}: {time.perf_counter() - t0:.3f} s", file=sys.stderr,
          flush=True)


def check_le(name: str, value: float, limit: float) -> Check:
    value = float(value)
    return Check(name, value, float(limit),
                 bool(np.isfinite(value) and value <= limit))


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    counts: Dict[str, Any]
    checks: List[Check]


#: seconds of the window that a traced run records, taken from its middle
TRACE_SECONDS = 5.0


class Spans:
    """The benchmark's host spans around calls into the program: kept in
    memory on the host clock and, while the profiler runs, written into
    its trace under the same names.

    With ``trace_dir`` set, the profiler records the part of the window
    from ``trace_from`` to ``trace_to`` seconds after :meth:`start`, begun
    and ended at the first dispatch boundary past each, so the trace
    holds whole dispatches.  ``traced`` is that part on the host clock.
    """

    def __init__(self, trace_dir: Optional[str] = None,
                 trace_from: float = 0.0, trace_to: float = 0.0):
        import jax.profiler

        self._profiler = jax.profiler
        self._dir, self._from, self._to = trace_dir, trace_from, trace_to
        self._open = None
        self.traced: Optional[tuple] = None
        self.spans: Dict[str, List[float]] = {}

    def start(self):
        self.t_start = time.perf_counter()

    def boundary(self):
        """Between two dispatches: begin or end the traced part."""
        if self._dir is None:
            return
        now = time.perf_counter() - self.t_start
        if self.traced is None and now >= self._from:
            from bench import trace as tr

            self._profiler.start_trace(self._dir,
                                       profiler_options=tr.profile_options())
            self._open = self._profiler.TraceAnnotation(tr.WINDOW_SPAN)
            self._open.__enter__()
            self.traced = (time.perf_counter(), None)
        elif self._open is not None and now >= self._to:
            self.stop()

    def stop(self):
        """End the traced part, if it is open."""
        if self._open is not None:
            self.traced = (self.traced[0], time.perf_counter())
            self._open.__exit__(None, None, None)
            self._open = None
            self._profiler.stop_trace()

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with self._profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader sees of a traced run."""
    config: dict
    trace: Any          # bench.trace.Trace of the traced part
    traced: tuple       # the traced part on the host clock
    spans: Dict[str, List[float]]
    counts: Dict[str, Any]
    peaks: dict

    def traced_calls(self) -> List[dict]:
        """The driver's recorded dispatches (``counts["calls"]``, each
        with host times ``t0`` and ``t_ready``) inside the traced part;
        none where the driver could not record them."""
        t0, t1 = self.traced
        return [c for c in self.counts.get("calls") or []
                if t0 <= c["t0"] and c["t_ready"] <= t1]

    def traced_spans(self, name: str) -> int:
        """How many host spans named ``name`` began in the traced part."""
        return sum(1 for n, s, _ in self.trace.host
                   if n == name and 0 <= s < self.trace.window_ns)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed directory ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, devices, *, t_start: float,
             peaks: Optional[dict] = None, log=None) -> dict:
    """Set up, measure and check one cell; return the result line.

    ``peaks`` is the device's row of :data:`bench.peaks.PEAKS`.  Without
    it (a run that is not on a chip) no metric is computed: the line
    carries ``correct`` and the checks only.
    """
    from bench import trace as tr

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = load_spec(root)
    cell = _entry(spec["workloads"], workload, "workload")
    devices = list(devices)[: cell["chips"]]
    traffic = load_traffic(root, cell["traffic"])
    ctx = Context(config=load_config(root, spec, cell["config"]),
                  params=traffic["params"], seed=int(seed),
                  seconds=float(seconds), devices=devices,
                  chips=cell["chips"])
    driver = load_driver(root, traffic["driver"])(ctx)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    log(f"set-up done in {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    middle = max(seconds - TRACE_SECONDS, 0.0) / 2
    spans = Spans(trace_dir, middle, middle + TRACE_SECONDS)
    spans.start()
    try:
        spans.boundary()
        driver.window(spans)
    finally:
        spans.stop()
    peak = memory_peak_bytes(devices)
    outcome = driver.finish()

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics: Dict[str, dict] = {}
    breakdown = None
    e2e, layer = cell_metrics(spec, workload)
    if trace:
        try:
            t = tr.load(tr.find_xplane(trace_dir),
                        devices=[d.id for d in devices])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_ns(t) / 1e9
        device["window_s"] = t.window_ns / 1e9
        breakdown = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}
        if peaks is not None:
            rec = RunRecord(config=ctx.config, trace=t,
                            traced=spans.traced, spans=spans.spans,
                            counts=outcome.counts, peaks=peaks)
            for m in layer:
                value = load_reader(root, m["name"])(rec)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    elif peaks is not None:
        values = dict(outcome.end_to_end, setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    correct = all(c.ok for c in outcome.checks) and bool(outcome.checks)
    line = {"correct": correct, "attempted": int(outcome.attempted),
            "failed": int(outcome.failed), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in outcome.checks}
    for c in outcome.checks:
        log(c.line())
    return line
