"""A benchmark tree at CPU size: the real drivers, readers and references
under tiny configurations, for the harness's tests."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")

FACES = {"name": "faces-tiny", "source": "https://arxiv.org/abs/2208.04817",
         "granularity": "direct26", "batched": True, "periodic": True,
         "points_per_rank": [8, 8, 8], "dtype": "float32",
         "damping": 0.03, "mode": "dataflow", "pack": "jnp"}

QWEN = {"hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 4, "vocab_size": 4096,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": True, "torch_dtype": "bfloat16",
        "param_dtype": "float32", "program_arch": "qwen1.5-0.5b"}

FACES_MIX = {"grid": [1, 1, 1], "iters_per_dispatch": 3,
             "warmup_dispatches": 1,
             "limits": {"field_rel_err": 1e-05, "resid_rel_err": 1e-05}}

# At this size the program's widest logit gap read 0 to 0.0069 over four
# seeds, the float8 control's 0.062 to 0.142: the limit lies between.
SERVE_MIX = {"prompt_len": 8, "max_new": 32, "slots": 2, "chunk": 8,
             "rate": 20.0, "arrival_seed": 0,
             "check_requests": 2, "limits": {"logit_gap": 0.03}}


def make_root(tmp, faces_mix=None, serve_mix=None, chips=1):
    """Write a benchmark tree under ``tmp`` with one Faces and one serving
    cell; the drivers, readers and references are the real ones."""
    b = os.path.join(tmp, "bench")
    os.makedirs(os.path.join(b, "configs"))
    os.makedirs(os.path.join(b, "traffic"))
    for sub in ("drivers", "metrics"):
        os.symlink(os.path.join(BENCH, sub), os.path.join(b, sub))
    files = {
        "bench/configs/faces-tiny.json": FACES,
        "bench/configs/qwen-tiny.json": QWEN,
        "bench/traffic/faces-mix.json": {
            "driver": "faces_restart", "params": faces_mix or FACES_MIX},
        "bench/traffic/serve-mix.json": {
            "driver": "serve_continuous", "params": serve_mix or SERVE_MIX},
    }
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [
            {"name": "faces-tiny", "source": "https://arxiv.org/abs/2208.04817",
             "file": "bench/configs/faces-tiny.json", "reduced": [],
             "why": "test"},
            {"name": "qwen-tiny", "source": "https://huggingface.co/Qwen/Qwen1.5-0.5B",
             "file": "bench/configs/qwen-tiny.json", "reduced": [],
             "why": "test"}],
        "workloads": [
            {"name": "faces", "config": "faces-tiny", "traffic": "faces-mix",
             "chips": chips, "why": "test"},
            {"name": "serve", "config": "qwen-tiny", "traffic": "serve-mix",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "iter_ms", "unit": "ms", "better": "lower",
             "bound": 0.05, "source": "host_clock", "workloads": ["faces"]},
            {"name": "tok_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock", "workloads": ["serve"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "faces.device_idle_share", "unit": "%",
             "better": "lower", "source": "device_trace", "layer": "device",
             "moves": "iter_ms", "workloads": ["faces"]},
            {"name": "chat.device_idle_share", "unit": "%",
             "better": "lower", "source": "device_trace", "layer": "device",
             "moves": "tok_per_s", "workloads": ["serve"]}],
    }
    files["BENCHMARK.json"] = spec
    for rel, obj in files.items():
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(obj, f)
    return tmp
