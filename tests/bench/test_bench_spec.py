"""BENCHMARK.json against the benchmark's rules, discovery by name, and
the refusal to measure without a chip."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.peaks import PEAKS, UnknownDevice, peaks_for

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = harness.load_spec(REPO)
NAMES = ([("config", c["name"]) for c in SPEC["configs"]]
         + [("workload", w["name"]) for w in SPEC["workloads"]]
         + [("traffic", w["traffic"]) for w in SPEC["workloads"]]
         + [("metric", m["name"]) for m in SPEC["end_to_end"]
            + SPEC["per_layer"]])


def test_benchmark_json_follows_the_rules():
    harness.validate(SPEC, REPO)


@pytest.mark.parametrize("kind,name", NAMES)
def test_name_charset(kind, name):
    assert harness.NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["end_to_end"]
                                    + SPEC["per_layer"]])
def test_unit_charset(metric):
    m = next(m for m in SPEC["end_to_end"] + SPEC["per_layer"]
             if m["name"] == metric)
    assert harness.UNIT.match(m["unit"])


ISSUE_CELLS = ["faces-256.1x1", "faces-256.2x2", "qwen1.5-0.5b.chat-poisson",
               "qwen1.5-0.5b.reasoning-backlog"]


def test_issue_cells_and_metrics():
    cells = [w["name"] for w in SPEC["workloads"]]
    assert cells == [c for c in ISSUE_CELLS if c in cells]
    assert cells[0] == "faces-256.1x1"
    assert all(w["chips"] == 1 or w["name"] == "faces-256.2x2"
               for w in SPEC["workloads"])
    # the issue's req_latency_p95_ms is a per-layer metric: host stalls
    # move it too far between runs for a bound
    assert {m["name"] for m in SPEC["end_to_end"]} == {
        "iter_ms", "tok_per_s", "setup_s"}
    for w in cells:
        _, layer = harness.cell_metrics(SPEC, w)
        assert any(m["name"].endswith("_roofline") or "mfu" in m["name"]
                   for m in layer), w


def _mutate(fn):
    spec = copy.deepcopy(SPEC)
    fn(spec)
    return spec


BROKEN = {
    "unit with a space": lambda s: s["end_to_end"][2].update(
        unit="tokens per second"),
    "name with a slash": lambda s: s["per_layer"][0].update(name="a/b"),
    "bound above 0.25": lambda s: s["end_to_end"][0].update(bound=0.3),
    "moves an unknown metric": lambda s: s["per_layer"][0].update(
        moves="nope"),
    "duplicate metric": lambda s: s["per_layer"].append(
        dict(s["per_layer"][0])),
    "no setup_s": lambda s: s["end_to_end"].pop(),
    "extra key": lambda s: s["per_layer"][0].update(why="x"),
    "cell that does not report the moved metric": lambda s: s["per_layer"][
        0].update(workloads=["qwen1.5-0.5b.chat-poisson"]),
    "unknown traffic": lambda s: s["workloads"][0].update(traffic="none"),
    "three chips": lambda s: s["workloads"][0].update(chips=3),
    "run_seconds too long": lambda s: s.update(run_seconds=60),
}


@pytest.mark.parametrize("what", sorted(BROKEN))
def test_validate_refuses(what):
    with pytest.raises(harness.SpecError):
        harness.validate(_mutate(BROKEN[what]), REPO)


FIXTURE_DRIVER = '''
from bench.harness import Outcome, check_le

class Driver:
    def __init__(self, ctx):
        self.ctx = ctx

    def setup(self):
        self.n = 0

    def window(self, spans):
        with spans("bench.fixture"):
            self.n = self.ctx.params["work"]

    def finish(self):
        return Outcome(attempted=self.n, failed=0,
                       end_to_end={"work_per_s": 2.0 * self.n},
                       counts={"x": self.n},
                       checks=[check_le("err", 0.0, 1.0)])
'''


def _fixture_root(tmp_path):
    """A benchmark tree holding one fixture cell and two fixture metrics,
    all found by name."""
    b = tmp_path / "bench"
    for sub in ("configs", "traffic", "drivers", "metrics"):
        (b / sub).mkdir(parents=True)
    (b / "configs" / "cfg.json").write_text("{}")
    (b / "traffic" / "mix.json").write_text(json.dumps(
        {"driver": "fixture_driver", "params": {"work": 21}}))
    (b / "drivers" / "fixture_driver.py").write_text(FIXTURE_DRIVER)
    (b / "metrics" / "fix.double_x.py").write_text(
        "def read(run):\n    return 2 * run.counts['x']\n")
    (b / "metrics" / "fix.nothing.py").write_text(
        "def read(run):\n    return None\n")
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "cfg", "source": "https://example.org/cfg",
                     "file": "bench/configs/cfg.json", "reduced": [],
                     "why": "fixture"}],
        "workloads": [{"name": "fix.cell", "config": "cfg",
                       "traffic": "mix", "chips": 1, "why": "fixture"}],
        "end_to_end": [
            {"name": "work_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "fix.double_x", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "fixture",
             "moves": "work_per_s"},
            {"name": "fix.nothing", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "fixture",
             "moves": "work_per_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)


def test_discovers_fixture_cell_and_metric_by_name(tmp_path):
    import time

    root = _fixture_root(tmp_path)
    harness.validate(harness.load_spec(root), root)
    fake = {"hbm_bytes_per_s": 1.0, "bf16_flops": 1.0}
    line = harness.run_cell(root, "fix.cell", 3, 0.1, False, [_Dev()],
                            t_start=time.perf_counter(), peaks=fake,
                            log=lambda msg: None)
    assert line["correct"] and line["attempted"] == 21
    assert line["metrics"]["work_per_s"]["value"] == 42.0
    assert set(line["metrics"]) == {"work_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    traced = harness.run_cell(root, "fix.cell", 3, 0.1, True, [_Dev()],
                              t_start=time.perf_counter(), peaks=fake,
                              log=lambda msg: None)
    # a reader that finds nothing is left out of the line
    assert traced["metrics"] == {"fix.double_x": {"value": 42.0,
                                                  "unit": "1"}}
    assert traced["device"]["window_s"] > 0


class _Dev:
    """Stands in for a chip in the fixture run (no JAX device needed)."""
    id, platform, device_kind = 0, "fixture", "fixture"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def test_unknown_device_kind_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v4")
    assert peaks_for("TPU v5 lite") is PEAKS["TPU v5 lite"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "faces-256.1x1",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_metric_without_a_chip():
    r = _run(REPO)
    assert r.returncode != 0
    assert "no accelerator" in r.stderr
    assert r.stdout.strip() == ""


def test_refuses_a_tree_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    r = _run(str(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""
