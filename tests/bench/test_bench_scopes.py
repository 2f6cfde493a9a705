"""The reduction of ``bench/tools/scopes.py``: device self time by the
program's named scopes, idle gaps labelled by the program's spans, and
the per-layer numbers they give, on synthetic traces with hand-computed
answers, and one run of the tool at CPU size."""

import tempfile

import pytest

import tiny
from bench import trace as tr
from bench.tools import scopes as sc


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_run_persistent)/while/body/closed_call/interior/jit(_roll)/slice",
     "interior"),
    ("jit(_run_persistent)/while/body/closed_call/unpack12/scatter-add",
     "unpack12"),
    ("jit(f)/while/body/closed_call/exchange/concatenate", "exchange"),
    ("jit(f)/while/body/closed_call/residual/reduce_sum", "residual"),
    ("jit(_admit_decode_fn)/admit/dot_general", "admit"),
    ("jit(_admit_decode_fn)/decode/while/body/dot_general", "decode"),
    ("jit(_run_persistent)/while/body/closed_call", "(none)"),
    ("admit", "(none)"),             # an argument named like a scope
    ("", "(none)"), (None, "(none)")])
def test_scope_is_the_innermost_program_scope(op_name, scope):
    assert sc.scope_of(op_name) == scope


HLO = """HloModule jit__run_persistent, is_scheduled=true

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/interior/add"}
}

ENTRY %main.9 (u: f32[8]) -> f32[8] {
  %u = f32[8]{0} parameter(0), metadata={op_name="u"}
  %fusion.96 = f32[8]{0} fusion(%u), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/while/body/interior/add"}
  %copy.7 = f32[8]{0} copy(%fusion.96)
  ROOT %dynamic-update-slice.546 = f32[8]{0} dynamic-update-slice(%copy.7), metadata={op_name="jit(f)/while/body/unpack2/scatter-add" stack_frame_id=4}
}
"""


def test_hlo_text_gives_each_instruction_its_scope():
    assert sc.hlo_scopes([HLO]) == {"jit__run_persistent": {
        "param_0": None, "add.1": "interior", "u": "(none)",
        "fusion.96": "interior", "copy.7": None,
        "dynamic-update-slice.546": "unpack2"}}
    assert sc.op_of("%fusion.96 = f32[256,256]{1,0:T(8,128)} fusion(%a)") \
        == "fusion.96"


def test_scope_self_times_count_each_op_once_and_sum_to_busy():
    # a loop op holding its body's ops, in two module executions, an op
    # between the executions and one no recorded HLO text holds;
    # window [0, 100)
    scopes = {"jit__run_persistent": {
        "while.1": "(none)", "fusion.96": "interior", "dus.2": "unpack0",
        "fusion.4": "residual", "fusion.5": "damp"}}
    ops = [("while.1", 0, 40), ("fusion.96", 2, 20), ("dus.2", 20, 25),
           ("fusion.4", 25, 30),
           ("copy.1", 45, 50),           # outside any execution
           ("while.1", 60, 110),         # execution 2's loop, clipped
           ("fusion.96", 60, 70), ("fusion.5", 70, 85), ("copy.9", 85, 90)]
    mods = [("jit__run_persistent(3)", 0, 40),
            ("jit__run_persistent(3)", 60, 110)]
    got, unscoped = sc.scope_times(ops, mods, scopes, 100.0)
    assert got == {"jit__run_persistent": {"(none)": 12 + 10, "interior": 28,
                                           "unpack0": 5, "residual": 5,
                                           "damp": 15, "(unknown)": 5},
                   "(none)": {"(unknown)": 5}}
    assert unscoped == {"jit__run_persistent/while.1": 22}
    busy = tr.busy_ns(tr.Trace(
        {0: tr.reduce_device([("x", s, e) for _, s, e in ops], 100.0)},
        [], 100.0))
    assert sum(t for m in got.values() for t in m.values()) == busy == 85


def test_an_op_without_op_name_takes_the_scope_it_runs_inside():
    scopes = {"jit__decode_fn": {"while.3": "decode", "fusion.8": "decode",
                                 "copy.212": None, "copy.5": None}}
    ops = [("copy.5", 0, 10),            # before the loop: no scope
           ("while.3", 10, 60), ("copy.212", 12, 30), ("fusion.8", 30, 50)]
    mods = [("jit__decode_fn(7)", 0, 60)]
    got, unscoped = sc.scope_times(ops, mods, scopes, 100.0)
    assert got == {"jit__decode_fn": {"(none)": 10, "decode": 50}}
    assert unscoped == {"jit__decode_fn/copy.5": 10}


def test_idle_gaps_keep_bench_labels_and_name_the_rest_by_program_span():
    busy = [(0, 20), (30, 60), (80, 90)]
    bench = [(tr.WINDOW_SPAN, 0, 100), ("bench.read", 18, 32)]
    program = [("st.serve.round", 0, 100), ("st.serve.emit", 55, 85)]
    assert sc.label_gaps(busy, 100.0, bench, program) == {
        "bench.read": 10, "st.serve.emit": 20, "st.serve.round": 10}
    # no program span: the benchmark's fallback label, as before
    assert sc.label_gaps(busy, 100.0, bench, []) == {
        "bench.read": 10, "host: outside benchmark spans": 30}


def test_span_summary_counts_spans_begun_in_the_window():
    spans = [("st.serve.round", -5, 10, {"admitted": 1, "decoded": 0}),
             ("st.serve.round", 10, 30e6, {"admitted": 2, "decoded": 16}),
             ("st.serve.round", 30e6, 50e6, {"admitted": 0, "decoded": 32}),
             ("st.serve.emit", 20e6, 21e6, {}),
             ("st.serve.round", 100e6, 120e6, {"admitted": 9})]
    assert sc.span_summary(spans, 100e6) == {
        "st.serve.round": {"n": 2, "ms": pytest.approx(25.0),
                           "args": {"admitted": 2, "decoded": 48}},
        "st.serve.emit": {"n": 1, "ms": pytest.approx(1.0), "args": {}}}


class _Result:
    def __init__(self, rid, t_arrive, t_admit, t_first):
        self.rid, self.t_arrive = rid, t_arrive
        self.t_admit, self.t_first = t_admit, t_first


def test_request_times_are_those_done_before_the_profiler():
    results = [_Result(0, 0.0, 0.1, 0.5), _Result(1, 1.0, 1.5, 1.75),
               _Result(2, 2.0, 2.0, 2.5)]
    done = [10.0, 11.0, 20.0]
    got = sc.request_times(results, done, before=15.0)
    assert got["queue_ms"] == pytest.approx([100.0, 500.0])
    assert got["ttft_ms"] == pytest.approx([500.0, 750.0])


def test_metrics_read_scopes_spans_and_counts():
    scopes = {"jit__run_persistent": {"interior": 1.2, "damp": 0.2,
                                      "pack0": 0.05, "unpack3": 0.1,
                                      "exchange": 0.15, "residual": 0.1,
                                      "(none)": 0.01},
              "jit_copy": {"(none)": 0.02}}
    spans = {"st.persistent.dispatch": {"n": 100, "ms": 1.0,
                                        "args": {"iters": 1000}}}
    got = sc.metrics(scopes, {"jit__run_persistent": 100}, spans, None, None)
    assert got == {"faces.stencil_ms_per_iter": pytest.approx(1.4),
                   "faces.halo_ms_per_iter": pytest.approx(0.3)}

    scopes = {"jit__admit_decode_fn": {"admit": 0.9, "decode": 3.0},
              "jit__decode_fn": {"decode": 2.0}}
    counts = {"admitted": 30, "prefill_rows": 320}
    requests = {"queue_ms": list(range(100)), "ttft_ms": [5.0] * 10}
    got = sc.metrics(scopes, {"jit__admit_decode_fn": 9}, {}, counts,
                     requests)
    assert got == {"chat.admit_prefill_ms": pytest.approx(100.0),
                   "chat.ttft_p95_ms": pytest.approx(5.0),
                   "chat.queue_wait_p95_ms": pytest.approx(94.05),
                   "chat.prefill_rows_per_admitted": pytest.approx(320 / 30)}


def test_metrics_leave_out_what_has_nothing_to_read():
    assert sc.metrics({}, {}, {}, None, None) == {}
    assert sc.metrics({"jit__admit_decode_fn": {"decode": 1.0}},
                      {"jit__admit_decode_fn": 3}, {},
                      {"admitted": 0, "prefill_rows": 0},
                      {"queue_ms": [], "ttft_ms": []}) == {}


@pytest.mark.parametrize("cell", ["faces", "serve"])
def test_the_tool_runs_a_cell_at_cpu_size(monkeypatch, cell):
    from bench.tools import limits

    monkeypatch.setattr(limits, "ROOT", tiny.make_root(tempfile.mkdtemp()))
    line = sc.run(cell, seed=2 ** 31 + 9, seconds=1.0)
    assert line["busy_s"] == 0.0      # no device plane on the CPU
    program = {"faces": "jit__run_persistent",
               "serve": "jit__admit_decode_fn"}[cell]
    assert line["hlo_instructions"][program] > 0
    spans = line["spans"]
    if cell == "faces":
        dispatch = spans["st.persistent.dispatch"]
        assert dispatch["args"]["iters"] == 3 * dispatch["n"] > 0
        # the engine's HostStats: one dispatch per span
        assert line["counts"] == {"dispatches": dispatch["n"],
                                  "sync_points": 0}
    else:
        rounds = spans["st.serve.round"]
        counts = line["counts"]
        assert counts["admitted"] == tiny.SERVE_MIX["rate"] * 1.0
        assert rounds["n"] == counts["rounds"]
        assert rounds["args"]["decoded"] == counts["decoded"]
        m = line["metrics"]
        assert m["chat.prefill_rows_per_admitted"] == pytest.approx(
            counts["prefill_rows"] / counts["admitted"])
