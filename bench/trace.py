"""Reduction from a profiler trace to device metrics.

The JAX profiler writes an ``.xplane.pb`` file.  :func:`load` reads it
with ``jax.profiler.ProfileData``.  On a TPU each device plane holds an
``XLA Ops`` line (operations; a ``while`` op encloses the operations of
its body, so events nest), an ``Async XLA Ops`` line and an ``XLA
Modules`` line (one event per program execution).  The events of each
device are reduced as they stream past into a :class:`DeviceTrace`: the
merged busy intervals, the self time of each operation, the collective
intervals and the module executions, all in nanoseconds relative to the
start of the benchmark's ``bench.traced`` host span and clipped to it.
:func:`reduce_device` does the same for plain lists, so the reduction is
tested on synthetic traces.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]

WINDOW_SPAN = "bench.traced"
HOST_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"

#: a collective, synchronous or as an async start/done pair
_COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")


def op_label(raw: str) -> str:
    """``%fusion.96 = f32[256,256]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.96 f32[256,256]``: the op's name and result type, without
    layouts and operands."""
    name, _, rest = raw.partition(" = ")
    name = name.strip().lstrip("%")
    if not rest:
        return name
    depth, out = 0, []
    for ch in rest:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            if ch == " " and out and out[0] != "(":
                break
            out.append(ch)
            if ch == ")" and out[0] == "(":
                break
    typ = "".join(out)
    return f"{name} {typ[:80]}"


def _collective(raw: str):
    m = _COLLECTIVE.match(raw.partition(" = ")[0].strip().lstrip("%"))
    return (m.group(1), m.group(2), m.group(3) or "") if m else None


@dataclasses.dataclass
class DeviceTrace:
    busy: List[Tuple[float, float]]
    self_ns: Dict[str, float]
    collectives: List[Tuple[float, float]]
    modules: List[Interval]


@dataclasses.dataclass
class Trace:
    devices: Dict[int, DeviceTrace]
    host: List[Interval]
    window_ns: float


# ---------------------------------------------------------------------------
# streaming reduction of one device's events
# ---------------------------------------------------------------------------


def merge(spans: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint ``(start, end)`` pairs."""
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, hi):
    return max(s, 0.0), min(e, hi)


class OutOfOrder(ValueError):
    """A line's events did not come sorted by start time."""


def _in_order(events):
    """The events, checked to come by start time with enclosing ones
    first (the profiler writes them so); a list is sorted first."""
    if isinstance(events, list):
        events = sorted(events, key=lambda x: (x[1], -x[2]))
    last = None
    for ev in events:
        key = (ev[1], -ev[2])
        if last is not None and key < last:
            raise OutOfOrder(ev)
        last = key
        yield ev


def reduce_device(ops: Iterable[Interval], window_ns: float,
                  async_ops: Iterable[Interval] = (),
                  modules: Iterable[Interval] = ()) -> DeviceTrace:
    """Reduce one device's events (times relative to the window start).

    ``ops`` are the ``XLA Ops`` events; nested ones (a loop's body inside
    the loop op) count once towards busy time and towards their own
    self time only.  Collectives are taken from ``ops`` and ``async_ops``.
    """
    busy: List[List[float]] = []
    self_ns: Dict[str, float] = collections.Counter()
    stack: List[list] = []          # [end, raw, start, child_ns]
    kinds: Dict[str, object] = {}
    colls: List[Interval] = []

    def close(frame):
        end, raw, start, child = frame
        lo, hi = _clip(start, end, window_ns)
        if hi > lo:
            self_ns[raw] += max((hi - lo) - child, 0.0)

    def is_collective(raw):
        if raw not in kinds:
            kinds[raw] = _collective(raw)
        return kinds[raw] is not None

    for ev in _in_order(ops):
        raw, s, e = ev
        if is_collective(raw):
            colls.append(ev)
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        lo, hi = _clip(s, e, window_ns)
        if stack and hi > lo:
            stack[-1][3] += hi - lo
        stack.append([e, raw, s, 0.0])
        if hi > lo:
            if busy and lo <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], hi)
            else:
                busy.append([lo, hi])
    while stack:
        close(stack.pop())

    colls += [ev for ev in async_ops if is_collective(ev[0])]
    spans = []
    pending: Dict[Tuple[str, str], float] = {}
    for raw, s, e in sorted(colls, key=lambda x: x[1]):
        coll, phase, num = kinds[raw]
        if phase == "-start":
            pending[(coll, num)] = s
        elif phase == "-done":
            spans.append((pending.pop((coll, num), s), e))
        else:
            spans.append((s, e))
    labels: Dict[str, float] = collections.Counter()
    for raw, t in self_ns.items():
        labels[op_label(raw)] += t
    return DeviceTrace(
        busy=[(s, e) for s, e in busy], self_ns=dict(labels),
        collectives=merge(_clip(s, e, window_ns) for s, e in spans),
        modules=[m for m in modules if 0.0 <= m[1] < window_ns])


# ---------------------------------------------------------------------------
# metrics of a reduced trace
# ---------------------------------------------------------------------------


def _mean(trace: Trace, fn) -> float:
    if not trace.devices:
        return 0.0
    return sum(fn(d) for d in trace.devices.values()) / len(trace.devices)


def busy_ns(trace: Trace) -> float:
    """Device-busy time in the window, averaged over the devices: the
    union of the intervals in which an operation ran."""
    return _mean(trace, lambda d: sum(e - s for s, e in d.busy))


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_ns(trace) / trace.window_ns


def read_idle_share(run) -> Optional[float]:
    """Per-layer reader: the share of the traced part with no operation
    on the device (%), averaged over the chips."""
    return 100.0 * idle_share(run.trace) if run.trace.devices else None


def collective_ns(trace: Trace) -> float:
    """Union of collective intervals in the window, averaged over devices."""
    return _mean(trace, lambda d: sum(e - s for s, e in d.collectives))


def module_executions(trace: Trace, pattern: str) -> List[float]:
    """Durations (ns) of the executions of XLA modules whose name matches
    the regular expression ``pattern``, on every device, that start
    inside the window."""
    rx = re.compile(pattern)
    return [e - s for d in trace.devices.values()
            for m, s, e in d.modules if rx.search(m)]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations with the most self time in the window
    (seconds, averaged over the devices)."""
    tot: Dict[str, float] = collections.Counter()
    for d in trace.devices.values():
        for name, t in d.self_ns.items():
            tot[name] += t
    k = max(len(trace.devices), 1)
    return [[name, t / k / 1e9] for name, t in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def _host_label(host: Sequence[Interval], t: float) -> str:
    """The innermost benchmark host span covering ``t``."""
    best = None
    for name, s, e in host:
        if s <= t < e and name != WINDOW_SPAN:
            if best is None or s >= best[1]:
                best = (name, s)
    return best[0] if best else "host: outside benchmark spans"


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """Idle device time in the window, grouped by what the host was doing
    at the middle of each gap (seconds, averaged over the devices), the
    ``n`` largest groups."""
    tot: Dict[str, float] = collections.Counter()
    for d in trace.devices.values():
        edges = [0.0] + [x for iv in d.busy for x in iv] + [trace.window_ns]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                tot[_host_label(trace.host, (lo + hi) / 2)] += hi - lo
    k = max(len(trace.devices), 1)
    return [[name, t / k / 1e9] for name, t in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------


def profile_options():
    """Profiler options for a traced window: device activity and the
    benchmark's own host spans, without the Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, devices: Optional[Sequence[int]] = None) -> Trace:
    """Read a profiler trace; keep the devices with ids in ``devices``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    t0, t1 = windows[0]

    def rel(events):
        return ((ev.name, ev.start_ns - t0, ev.end_ns - t0) for ev in events)

    out: Dict[int, DeviceTrace] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or (devices is not None and int(m.group(1)) not in devices):
            continue
        lines = {line.name: line for line in plane.lines}

        def events(name):
            return rel(lines[name].events) if name in lines else ()

        kw = dict(async_ops=list(events(ASYNC_LINE)),
                  modules=list(events(MODULES_LINE)))
        try:
            dev = reduce_device(events(OPS_LINE), t1 - t0, **kw)
        except OutOfOrder:
            dev = reduce_device(list(events(OPS_LINE)), t1 - t0, **kw)
        out[int(m.group(1))] = dev
    return Trace(devices=out,
                 host=[(n, s - t0, e - t0) for n, s, e in host],
                 window_ns=t1 - t0)
