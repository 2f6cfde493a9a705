"""The reduction from a profiler trace to device metrics, on synthetic
traces with hand-computed answers and on one recorded on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr


def _trace(ops, modules=None, host=None, window=100.0, async_ops=None):
    devs = {d: tr.reduce_device(o, window, (async_ops or {}).get(d, ()),
                                (modules or {}).get(d, ()))
            for d, o in ops.items()}
    return tr.Trace(devices=devs, host=host or [], window_ns=window)


def test_merge():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]


def test_busy_and_idle_are_an_interval_union_averaged_over_devices():
    t = _trace({0: [("a", -10, 40), ("b", 30, 50), ("c", 90, 120)],
                1: [("a", 10, 30)]})
    # device 0: [0,50] + [90,100] = 60; device 1: 20
    assert tr.busy_ns(t) == pytest.approx(40.0)
    assert tr.idle_share(t) == pytest.approx(0.6)


def test_nested_ops_count_once_and_report_self_time():
    ops = [("%while.1 = (s32[]) while(...)", 0, 50),
           ("%fusion.2 = f32[4,4]{1,0:T(8,128)} fusion(%p)", 5, 20),
           ("%fusion.3 = f32[8]{0} fusion(%q)", 20, 30),
           ("%copy.4 = f32[8]{0} copy(%r)", 60, 70)]
    t = _trace({0: ops})
    assert tr.busy_ns(t) == 60
    assert tr.top_ops(t) == [["while.1 (s32[])", 25e-9],
                             ["fusion.2 f32[4,4]", 15e-9],
                             ["fusion.3 f32[8]", 10e-9],
                             ["copy.4 f32[8]", 10e-9]]


def test_out_of_order_events_are_sorted():
    ops = [("b", 20, 30), ("a", 0, 10), ("c", 25, 40)]
    assert tr.busy_ns(_trace({0: ops})) == 30


def test_module_executions_by_name():
    mods = {0: [("jit__admit_decode_fn(3)", 0, 30),
                ("jit__decode_fn(4)", 40, 50),
                ("jit__decode_fn(4)", 60, 75),
                ("jit__decode_fn(4)", 150, 160)]}
    t = _trace({0: []}, modules=mods)
    assert tr.module_executions(t, r"^jit__decode_fn\b") == [10, 15]
    assert tr.module_executions(t, r"^jit__admit_decode_fn\b") == [30]


def test_collectives_pair_start_and_done_across_lines():
    ops = [("%collective-permute-start.1 = (f32[2]) collective-permute-start(%a)", 10, 12),
           ("%fusion.3 = f32[2] fusion(%b)", 12, 30),
           ("%collective-permute-done.1 = f32[2] collective-permute-done(%c)", 30, 31),
           ("%all-reduce.7 = f32[] all-reduce(%d)", 60, 62),
           ("%all-reduce-scatter-fusion = f32[] fusion(%e)", 70, 80)]
    asyncs = [("%collective-permute-start.2 = (f32[2]) x", 20, 21),
              ("%collective-permute-done.2 = f32[2] y", 40, 45)]
    t = _trace({0: ops, 1: ops}, async_ops={0: asyncs, 1: asyncs})
    assert t.devices[0].collectives == [(10, 45), (60, 62)]
    assert tr.collective_ns(t) == pytest.approx(35 + 2)


def test_idle_gaps_by_host_activity():
    ops = {0: [("f.1", 0, 20), ("f.2", 20, 30), ("f.1", 60, 80)]}
    host = [(tr.WINDOW_SPAN, 0, 100), ("bench.read", 25, 70),
            ("bench.enqueue", 85, 95)]
    t = _trace(ops, host=host)
    assert tr.idle_gaps(t) == [["bench.read", 30e-9],
                               ["bench.enqueue", 20e-9]]


def test_a_recorded_trace_reads_back(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=tr.profile_options())
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.call"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    calls = [(s, e) for n, s, e in t.host if n == "bench.call"]
    assert len(calls) == 3
    assert 0 <= calls[0][0] < calls[-1][1] <= t.window_ns
    assert t.devices == {}   # no TPU in this trace: nothing counts as busy
    assert tr.busy_ns(t) == 0.0
