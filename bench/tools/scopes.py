#!/usr/bin/env python3
"""Device time by program scope, and the program's own spans, in one
traced run of a cell, on the chip.

    python3 bench/tools/scopes.py --workload faces-256.1x1 --seed 7 \
        --seconds 20

One process sets the cell up and runs its window as a traced run of the
benchmark does (the middle 5 s under the profiler), keeps the trace and
prints one JSON line:

* ``scopes``: each device's self time (s, averaged over the devices) by
  XLA module and by the innermost named scope the program gave an op
  (:data:`SCOPE`): a queue op's name, ``exchange``, ``wait`` and
  ``residual`` in Faces, ``admit`` and ``decode`` in serving.  The
  trace's op events carry no ``op_name``, so it is read from the
  compiled HLO text of each program the window dispatched (a fusion
  carries its root's), all compiled afresh in this process.  An op the
  compiler left without an ``op_name`` takes the scope of the op it runs
  inside; an ``op_name`` without a scope counts under ``(none)`` (its
  largest ops in ``unscoped_ops``), an op of a program not recorded
  under ``(unknown)``.
* ``busy_s`` and ``idle_gaps``: device idle time labelled, as the
  benchmark labels it, by the innermost ``bench.*`` host span, and where
  none covers it by the innermost ``st.*`` span of the program.
* ``spans``: the program's ``st.*`` host spans begun in the traced part,
  with their count, mean length and the sums of their arguments.
* ``counts``: the engine's stats over the window.
* ``metrics``: the per-layer numbers these feed (:func:`metrics`).

The benchmark's own runs never run this; it leaves every file the
benchmark reads as it is.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import contextlib
import dataclasses
import json
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the named scopes the program gives its ops
SCOPE = re.compile(r"^(pack\d+|unpack\d+|interior|damp|exchange|wait|"
                   r"residual|admit|decode)$")
NONE, UNKNOWN = "(none)", "(unknown)"
PROGRAM_PREFIX = "st."
_MODULE = re.compile(r"^HloModule (\S+?),")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?(\S+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')


def scope_of(op_name: Optional[str]) -> str:
    """The innermost program scope in an HLO ``op_name`` path, e.g.
    ``jit(f)/while/body/interior/jit(_roll)/slice`` -> ``interior``.  The
    last component names the op itself (or an argument), never a scope."""
    for part in reversed((op_name or "").split("/")[:-1]):
        if SCOPE.match(part):
            return part
    return NONE


def hlo_scopes(texts: Iterable[str]) -> Dict[str, Dict[str, Optional[str]]]:
    """Per HLO module, the scope of each instruction, from compiled HLO
    texts (None where the compiler left no ``op_name``, as on copies of
    its own); a later text of the same module overrides an earlier
    one."""
    out: Dict[str, Dict[str, Optional[str]]] = {}
    for text in texts:
        scopes = None
        for line in text.splitlines():
            m = _MODULE.match(line)
            if m:
                scopes = out.setdefault(m.group(1), {})
                continue
            m = _INSTRUCTION.match(line)
            if m and scopes is not None:
                name = _OP_NAME.search(line)
                scopes[m.group(1)] = name and scope_of(name.group(1))
    return out


def module_of(name: str) -> str:
    """``jit__decode_fn(12)`` -> ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_of(event_name: str) -> str:
    """``%fusion.96 = f32[...] fusion(...)`` -> ``fusion.96``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def scope_times(ops: Iterable[Tuple[str, float, float]],
                modules: Sequence[Tuple[str, float, float]],
                scopes: Dict[str, Dict[str, Optional[str]]],
                window_ns: float):
    """Self time (ns) of one device's ops by module and scope, and of
    each op counted under ``(none)``.

    ``ops`` are ``(op, start, end)`` in the window's time; an op belongs
    to the module execution it starts in and takes its scope from
    ``scopes`` (:func:`hlo_scopes`).  An op the compiler left without an
    ``op_name`` takes the scope of the op it runs inside (a copy in a
    loop's body: the loop's).  An op nested in another counts once,
    towards its own scope, as in :func:`bench.trace.reduce_device`."""
    from bench import trace as tr

    mods = sorted((s, e, module_of(m)) for m, s, e in modules)
    starts = [s for s, _, _ in mods]

    def module(t):
        k = bisect.bisect_right(starts, t) - 1
        return mods[k][2] if k >= 0 and t < mods[k][1] else NONE

    labelled, stack = [], []        # stack: (end, scope) of open ops
    for op, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        mod = module(s)
        scope = scopes.get(mod, {}).get(op, UNKNOWN)
        if scope is None:
            scope = stack[-1][1] if stack else NONE
        stack.append((e, scope))
        key = f"{mod}\x00{scope}"
        labelled.append((key + f"\x00{op}" if scope == NONE else key, s, e))
    dev = tr.reduce_device(labelled, window_ns)
    out: Dict[str, Dict[str, float]] = collections.defaultdict(dict)
    unscoped: Dict[str, float] = {}
    for key, t in dev.self_ns.items():
        mod, scope, *op = key.split("\x00")
        out[mod][scope] = out[mod].get(scope, 0.0) + t
        if op:
            unscoped[f"{mod}/{op[0]}"] = t
    return dict(out), unscoped


def label_gaps(busy: Sequence[Tuple[float, float]], window_ns: float,
               bench: Sequence[Tuple[str, float, float]],
               program: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Idle time (ns) of one device by the innermost ``bench.*`` host
    span at each gap's middle; where none covers it, by the innermost
    program span."""
    from bench import trace as tr

    fallback = tr._host_label((), 0.0)
    out: Dict[str, float] = collections.Counter()
    edges = [0.0] + [x for iv in busy for x in iv] + [window_ns]
    for lo, hi in zip(edges[::2], edges[1::2]):
        if hi > lo:
            mid = (lo + hi) / 2
            label = tr._host_label(bench, mid)
            if label == fallback:
                label = tr._host_label(program, mid)
            out[label] += hi - lo
    return dict(out)


def span_summary(spans: Sequence[Tuple[str, float, float, dict]],
                 window_ns: float) -> Dict[str, dict]:
    """Per program span name begun in the window: count, mean length
    (ms) and the sums of its numeric arguments."""
    out: Dict[str, dict] = {}
    for name, s, e, args in spans:
        if not 0 <= s < window_ns:
            continue
        o = out.setdefault(name, {"n": 0, "ms": 0.0, "args": {}})
        o["n"] += 1
        o["ms"] += (e - s) / 1e6
        for k, v in args.items():
            if isinstance(v, (int, float)):
                o["args"][k] = o["args"].get(k, 0) + v
    for o in out.values():
        o["ms"] /= o["n"]
    return out


def p95(values: Sequence[float]) -> Optional[float]:
    return float(np.percentile(values, 95)) if len(values) else None


def request_times(results, done: Sequence[float],
                  before: float) -> Dict[str, List[float]]:
    """Per request finished (host clock ``done``) before ``before``: its
    wait for admission and its time to first token (ms), both from its
    scheduled arrival, on the program's clock."""
    out = {"queue_ms": [], "ttft_ms": []}
    for r in results:
        if done[r.rid] < before:
            out["queue_ms"].append((r.t_admit - r.t_arrive) * 1e3)
            out["ttft_ms"].append((r.t_first - r.t_arrive) * 1e3)
    return out


def metrics(scopes: Dict[str, Dict[str, float]],
            executions: Dict[str, float], spans: Dict[str, dict],
            counts: Optional[dict], requests: Optional[dict],
            admit_module: str = "jit__admit_decode_fn") -> Dict[str, float]:
    """The per-layer numbers of the program's scopes, spans and counters.

    ``scopes`` are seconds by module and scope, ``executions`` the runs
    of each module begun in the window.  Faces' scopes count per
    iteration, over the iterations of the ``st.persistent.dispatch``
    spans begun in the window; the admission's scope per execution of
    ``admit_module``."""
    out: Dict[str, float] = {}
    total: Dict[str, float] = collections.Counter()
    for by_scope in scopes.values():
        for scope, t in by_scope.items():
            total[scope] += t
    iters = spans.get("st.persistent.dispatch", {}).get("args", {}).get(
        "iters")
    if iters:
        def per_iter(pattern):
            rx = re.compile(pattern)
            return 1e3 * sum(t for s, t in total.items()
                             if rx.match(s)) / iters
        out["faces.stencil_ms_per_iter"] = per_iter(r"^(interior|damp)$")
        out["faces.halo_ms_per_iter"] = per_iter(
            r"^(pack\d+|unpack\d+|exchange)$")
    runs = executions.get(admit_module)
    admit = scopes.get(admit_module, {}).get("admit")
    if runs and admit is not None:
        out["chat.admit_prefill_ms"] = 1e3 * admit / runs
    if requests:
        for name, key in (("chat.ttft_p95_ms", "ttft_ms"),
                          ("chat.queue_wait_p95_ms", "queue_ms")):
            value = p95(requests[key])
            if value is not None:
                out[name] = value
    if counts and counts.get("admitted"):
        out["chat.prefill_rows_per_admitted"] = (counts["prefill_rows"]
                                                 / counts["admitted"])
    return out


class _Recorder:
    """Stands in for a jitted program: counts its calls by the abstract
    signature of their arguments, then calls through."""

    def __init__(self, fn):
        self.fn = fn
        self.signatures: Dict[str, list] = {}

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, *args):
        import jax

        # the format holds the layout too: a state that a program
        # returned may be laid out otherwise, a program of its own
        sig = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.format,
            weak_type=x.weak_type), args)
        self.signatures.setdefault(repr(sig), [0, sig])[0] += 1
        return self.fn(*args)

    def texts(self) -> List[str]:
        """Compiled HLO text per signature called, the most called last."""
        return [self.fn.lower(*sig).compile().as_text()
                for _, sig in sorted(self.signatures.values(),
                                     key=lambda c: c[0])]


def _record(eng) -> List[_Recorder]:
    """Put a recorder in place of each jitted program of ``eng``: a
    persistent engine's loop, a serving engine's counted programs."""
    if hasattr(eng, "_jitted"):
        eng._jitted = _Recorder(eng.compile())
        return [eng._jitted]
    recs = []
    for fn in vars(eng).values():
        if hasattr(fn, "calls"):
            counted = fn._fn if hasattr(fn._fn, "calls") else fn
            counted._fn = _Recorder(counted._fn)
            recs.append(counted._fn)
    return recs


def read(path: str, devices: Sequence[int]):
    """From a profiler file: per device the ops as ``(op, start, end)``
    and the module executions, and the program's spans as ``(name,
    start, end, args)``, all relative to the benchmark's traced window,
    and its length."""
    from jax.profiler import ProfileData

    from bench import trace as tr

    pd = ProfileData.from_file(path)
    spans, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tr.WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(PROGRAM_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.end_ns,
                                  dict(ev.stats)))
    t0, t1 = window
    per_device = {}
    for plane in pd.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in devices:
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name not in (tr.MODULES_LINE, tr.OPS_LINE):
                continue
            events = [(ev.name, ev.start_ns - t0, ev.end_ns - t0)
                      for ev in line.events]
            if line.name == tr.MODULES_LINE:
                mods = events
            elif line.name == tr.OPS_LINE:
                ops = [(op_of(n), s, e) for n, s, e in events]
        per_device[int(m.group(1))] = (ops, mods)
    spans = [(n, s - t0, e - t0, a) for n, s, e, a in spans]
    return per_device, spans, t1 - t0


def _stats(eng) -> Optional[dict]:
    stats = getattr(eng, "stats", None)
    if stats is None or not dataclasses.is_dataclass(stats):
        return None
    return dataclasses.asdict(stats)


@contextlib.contextmanager
def _no_persistent_cache():
    """JAX's persistent cache keys a program without its metadata, so a
    program loaded from it may carry the ``op_name`` of another build of
    the same computation: compile everything afresh meanwhile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def run(workload: str, seed: int, seconds: float) -> dict:
    """Set the cell up, run its window with the middle traced, and
    reduce the trace as the module's docstring says."""
    with _no_persistent_cache():
        return _run(workload, seed, seconds)


def _run(workload: str, seed: int, seconds: float) -> dict:
    from bench import harness
    from bench import trace as tr
    from bench.tools.limits import _driver

    _, d = _driver(workload, seed, seconds)
    d.setup()
    eng = d.eng
    recorders = _record(eng)
    devices = [x.id for x in d.ctx.devices]
    trace_dir = tempfile.mkdtemp(prefix="bench_scopes_")
    middle = max(seconds - harness.TRACE_SECONDS, 0.0) / 2
    spans = harness.Spans(trace_dir, middle, middle + harness.TRACE_SECONDS)
    before = _stats(eng)
    spans.start()
    try:
        spans.boundary()
        d.window(spans)
    finally:
        spans.stop()
    after = _stats(eng)
    counts = (None if before is None else
              {k: after[k] - before[k] for k in after})
    with getattr(d, "mesh", None) or contextlib.nullcontext():
        hlo = hlo_scopes(t for r in recorders for t in r.texts())

    path = tr.find_xplane(trace_dir)
    per_device, program, window_ns = read(path, devices)
    bench = tr.load(path, devices=devices)
    shutil.rmtree(trace_dir, ignore_errors=True)
    k = max(len(per_device), 1)
    scopes: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    executions: Dict[str, float] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    unscoped: Dict[str, float] = collections.Counter()
    names = [(n, s, e) for n, s, e, _ in program]
    for dev_id, (ops, mods) in per_device.items():
        times, none_ops = scope_times(ops, mods, hlo, window_ns)
        for mod, by_scope in times.items():
            for scope, t in by_scope.items():
                scopes[mod][scope] += t / k / 1e9
        for op, t in none_ops.items():
            unscoped[op] += t / k / 1e9
        for mod, s, _ in mods:
            if 0 <= s < window_ns:
                executions[module_of(mod)] += 1 / k
        for label, t in label_gaps(bench.devices[dev_id].busy, window_ns,
                                   bench.host, names).items():
            gaps[label] += t / k / 1e9
    requests = None
    if hasattr(d, "results"):
        requests = request_times(d.results, d.done, spans.traced[0])
    summary = span_summary(program, window_ns)
    return {
        "workload": workload, "seed": seed,
        "busy_s": tr.busy_ns(bench) / 1e9, "window_s": window_ns / 1e9,
        "scopes": {m: dict(v) for m, v in scopes.items()},
        "executions": dict(executions),
        "idle_gaps": dict(gaps), "spans": summary, "counts": counts,
        "hlo_instructions": {m: len(v) for m, v in hlo.items()},
        "unscoped_ops": dict(sorted(unscoped.items(),
                                    key=lambda x: -x[1])[:10]),
        "metrics": metrics(scopes, executions, summary, counts, requests),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    print(json.dumps(run(args.workload, args.seed, args.seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
