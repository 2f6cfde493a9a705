"""The benchmark's operation and byte counts against hand-computed
shapes."""

import json
import os

import pytest

from bench import counts

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
QWEN = json.load(open(os.path.join(REPO, "bench/configs/qwen1.5-0.5b.json")))


def test_faces_slabs_and_least_bytes():
    # 6 faces of 256x256, 12 edges of 256, 8 corners
    assert counts.faces_slab_points((256, 256, 256)) == 6 * 65536 + 12 * 256 + 8
    assert counts.faces_slab_points((4, 5, 6)) == (
        2 * (5 * 6 + 4 * 6 + 4 * 5) + 4 * (4 + 5 + 6) + 8)
    field = 256 ** 3
    assert counts.faces_iter_min_bytes((256,) * 3, 4) == 4 * (
        2 * field + 2 * (6 * 65536 + 12 * 256 + 8))
    # about 132 MiB: 2 x 64 MiB field plus about 3 MiB of slabs
    assert 131 < counts.faces_iter_min_bytes((256,) * 3, 4) / 2 ** 20 < 132


def test_qwen_parameter_count():
    s = counts.transformer_sizes(QWEN)
    # per layer: q,k,v,o 4 x 1024^2, biases 3 x 1024, MLP 3 x 1024 x 2816,
    # two norms; a tied 151936 x 1024 embedding and the final norm
    layer = 4 * 1024 ** 2 + 3 * 1024 + 3 * 1024 * 2816 + 2 * 1024
    assert s["layer"] == layer == 12_850_176
    assert s["total"] == 24 * layer + 151936 * 1024 + 1024 == 463_987_712
    assert counts.param_bytes(QWEN, 4) == 4 * 463_987_712
    assert counts.kv_bytes_per_position(QWEN, 2) == 2 * 24 * 16 * 64 * 2


def test_parameter_count_matches_the_program_layout():
    """The count from the published keys equals the leaves the program
    allocates, for a small configuration of the same family."""
    import jax
    import numpy as np

    from bench.drivers.serve_continuous import model_config
    from repro.models import Model

    cfg = dict(QWEN, hidden_size=64, intermediate_size=96,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, vocab_size=300)
    sds, _ = Model(model_config(cfg)).abstract_init()
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(sds))
    assert n == counts.transformer_sizes(cfg)["total"]


def test_decode_steps_and_kv_replay():
    # a slot admitted with a 4-token prompt attends over 5, 6, 7 ... entries
    calls = [([True, False], [2, 0]),     # admit slot 0, 2 steps
             ([False, False], [1, 0]),    # one more step for slot 0
             ([False, True], [1, 2])]     # admit slot 1 while 0 finishes
    assert counts.decode_steps_kv(calls, 4, 2) == [
        (2, 5 + 6), (1, 7), (2, 8 + 5 + 6)]


def test_flops_and_serving_bytes():
    cfg = dict(QWEN, num_hidden_layers=1, hidden_size=8, intermediate_size=16,
               num_attention_heads=2, num_key_value_heads=2, vocab_size=10)
    s = counts.transformer_sizes(cfg)
    matmul = 4 * 64 + 3 * 8 * 16
    assert s["layer_matmul"] == matmul
    assert counts.token_flops(cfg, ctx=3, logits=False) == 2 * matmul + 4 * 8 * 3
    assert counts.token_flops(cfg, ctx=1, logits=True) == (
        2 * matmul + 4 * 8 + 2 * 8 * 10)
    assert counts.prefill_flops(cfg, 2) == (
        counts.token_flops(cfg, 1, False) + counts.token_flops(cfg, 2, True))
    calls = [{"kind": "admit", "steps": 2, "kv": 11, "admitted": 1,
              "decoded": 2},
             {"kind": "decode", "steps": 1, "kv": 7, "admitted": 0,
              "decoded": 1}]
    pb, kvb = counts.param_bytes(cfg, 4), counts.kv_bytes_per_position(cfg, 2)
    assert counts.serve_min_bytes(cfg, calls, 4) == (
        3 * pb + 11 * kvb + 7 * kvb + pb + 4 * kvb)
    assert counts.serve_min_bytes(cfg, calls, 4, kinds=("decode",)) == (
        pb + 7 * kvb)
    assert counts.serve_useful_flops(cfg, calls, 4) == (
        counts.prefill_flops(cfg, 4) + 3 * (2 * matmul + 2 * 8 * 10)
        + 4 * 8 * 18)
