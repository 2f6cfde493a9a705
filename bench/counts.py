"""Operations and bytes of the benchmark's work, counted from shapes.

Every function returns the algorithm's *minimum*: bytes that must cross
HBM at least once and multiply-adds that the mathematics needs.  A
roofline share computed from them can therefore not exceed 100% unless
the measured time leaves out part of the work.
"""

from __future__ import annotations

import itertools
from typing import Iterable, Sequence, Tuple

# ---------------------------------------------------------------------------
# Faces (26-neighbour direct-stiffness exchange on a 3-D block per rank)
# ---------------------------------------------------------------------------

DIRECTIONS26 = tuple(d for d in itertools.product((-1, 0, 1), repeat=3)
                     if any(d))


def faces_slab_points(points: Sequence[int]) -> int:
    """Points in the 26 boundary slabs of one block (faces, edges and
    corners, each at its own size)."""
    total = 0
    for d in DIRECTIONS26:
        n = 1
        for s, p in zip(d, points):
            n *= 1 if s else p
        total += n
    return total


def faces_iter_min_bytes(points: Sequence[int], itemsize: int) -> int:
    """Least HBM traffic of one Faces iteration on one rank.

    One read and one write of the field (stencil, boundary sums, damping
    and residual fused into a single pass), plus each of the 26 message
    slabs written once when packed and read once when unpacked.
    """
    field = 1
    for p in points:
        field *= p
    return itemsize * (2 * field + 2 * faces_slab_points(points))


# ---------------------------------------------------------------------------
# decoder-only transformer (qwen1.5 layout: GQA attention + gated MLP)
# ---------------------------------------------------------------------------


def transformer_sizes(cfg: dict) -> dict:
    """Parameter counts of a dense decoder from its published config keys."""
    d = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    f = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d   # q, k, v, o
    bias = (h + 2 * kv) * hd                            # q, k, v bias
    mlp = 3 * d * f                                     # gate, up, down
    norms = 2 * d
    layer = attn + bias + mlp + norms
    embed = V * d
    head = 0 if cfg.get("tie_word_embeddings") else V * d
    return {
        "layer_matmul": attn + mlp,
        "layer": layer,
        "embed": embed,
        "head": head,
        "total": L * layer + embed + head + d,
        "d": d, "L": L, "h": h, "kv": kv, "hd": hd, "V": V,
    }


def param_bytes(cfg: dict, itemsize: int) -> int:
    """Bytes of all parameters as stored."""
    return transformer_sizes(cfg)["total"] * itemsize


def kv_bytes_per_position(cfg: dict, itemsize: int) -> int:
    """Bytes of K and V of one sequence position over all layers."""
    s = transformer_sizes(cfg)
    return 2 * s["L"] * s["kv"] * s["hd"] * itemsize


def attention_flops(cfg: dict, ctx: int) -> int:
    """Score and value FLOPs of one query position attending to ``ctx``
    positions, all layers."""
    s = transformer_sizes(cfg)
    return 4 * s["L"] * s["h"] * s["hd"] * ctx


def token_flops(cfg: dict, ctx: int, logits: bool) -> int:
    """Useful FLOPs of one token at context length ``ctx`` (itself
    included): the layer matmuls, attention over the context, and the
    vocabulary projection where its logits are used."""
    s = transformer_sizes(cfg)
    f = 2 * s["L"] * s["layer_matmul"] + attention_flops(cfg, ctx)
    if logits:
        f += 2 * s["d"] * s["V"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """Useful FLOPs of prefilling one prompt (causal attention, logits of
    the last position only)."""
    return sum(token_flops(cfg, t + 1, logits=(t == prompt_len - 1))
               for t in range(prompt_len))


def decode_steps_kv(calls: Iterable[Tuple[Sequence[bool], Sequence[int]]],
                    prompt_len: int, slots: int):
    """Replay a serving run's dispatches and count its decode work.

    ``calls`` holds, per dispatch in order, the admitted-slot mask (all
    False for a pure decode dispatch) and the decode steps each slot ran.
    Returns ``[(steps, kv_entries_read)]`` per dispatch, where ``steps``
    is the loop trip count (the most steps of any slot) and
    ``kv_entries_read`` the valid cache entries that the steps attended
    over, summed over slots.
    """
    length = [0] * slots
    out = []
    for admit, n in calls:
        kv = 0
        for s in range(slots):
            if admit[s]:
                length[s] = prompt_len
            for _ in range(int(n[s])):
                length[s] += 1
                kv += length[s]
        out.append((max(int(x) for x in n) if len(n) else 0, kv))
    return out


def _itemsize(name: str) -> int:
    return {"float32": 4, "bfloat16": 2, "float16": 2}[name]


def serve_min_bytes(cfg: dict, calls, prompt_len: int,
                    kinds=("admit", "decode")) -> int:
    """Least HBM traffic of a serving run's dispatches of the given kinds.

    Each of ``calls`` (a driver's recorded dispatches) gives its loop
    ``steps``, the valid KV entries ``kv`` its steps attended over and the
    slots it ``admitted``.  Each decode step reads every parameter as
    stored and the valid KV it attends over; each admission reads the
    parameters once more for its prefill and writes the admitted prompts'
    KV.
    """
    pb = param_bytes(cfg, _itemsize(cfg["param_dtype"]))
    kvb = kv_bytes_per_position(cfg, _itemsize(cfg["torch_dtype"]))
    total = 0
    for c in calls:
        if c["kind"] in kinds:
            total += c["steps"] * pb + c["kv"] * kvb
            if c["kind"] == "admit":
                total += pb + c["admitted"] * prompt_len * kvb
    return total


def serve_useful_flops(cfg: dict, calls, prompt_len: int) -> int:
    """FLOPs of the prompts prefilled for admitted requests and of the
    tokens decoded by ``calls``; prefill rows of slots that were not
    admitted do not count."""
    s = transformer_sizes(cfg)
    per_token = 2 * s["L"] * s["layer_matmul"] + 2 * s["d"] * s["V"]
    attn = 4 * s["L"] * s["h"] * s["hd"]
    return sum(c["admitted"] * prefill_flops(cfg, prompt_len)
               + c["decoded"] * per_token + attn * c["kv"] for c in calls)
