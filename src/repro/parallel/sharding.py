"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Model code names array dimensions with *logical* axes ("batch", "embed",
"heads", "expert", ...).  A rule table maps logical axes to mesh axes
("pod", "data", "model") per execution regime.  The launcher resolves
params/inputs/outputs to ``NamedSharding`` through these tables; model
internals use :func:`shard_constraint` for activation hints.

Regimes
-------
``RULES_TRAIN``       — batch over (pod×)data, tensor/expert over model,
                        parameters FSDP-sharded over (pod×)data on their
                        largest non-model dim (ZeRO-3 style).
``RULES_DECODE``      — decode batch over (pod×)data, KV heads over model.
``RULES_LONG_DECODE`` — batch=1: the KV/state *sequence* shards over
                        (pod×)data instead of batch.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """jax.make_mesh with explicit Auto axis types (silences the 0.9
    behaviour-change warning; we use shard_map/pjit auto mode)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=auto)
    dev = np.asarray(devices).reshape(tuple(shape))
    return Mesh(dev, tuple(axes), axis_types=auto)


@dataclasses.dataclass(frozen=True)
class LogicalRules:
    """Ordered logical→mesh mapping.  First match wins per logical axis;
    a mesh axis may appear at most once in one PartitionSpec, so
    `logical_spec` drops later duplicate mesh axes."""

    rules: Tuple[Tuple[str, MeshAxes], ...]
    name: str = "rules"

    def mesh_axes(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        for k, v in self.rules:
            if k == logical:
                return v
        return None

    def replace(self, **updates: MeshAxes) -> "LogicalRules":
        new = [(k, updates.pop(k)) if k in updates else (k, v)
               for k, v in self.rules]
        for k, v in updates.items():
            new.append((k, v))
        return LogicalRules(tuple(new), name=self.name + "*")


def logical_spec(axes: Sequence[Optional[str]], rules: LogicalRules,
                 mesh: Optional[Mesh] = None) -> P:
    """Resolve a tuple of logical axis names to a PartitionSpec.

    Mesh axes already used by an earlier dim are dropped (a mesh axis can
    shard only one dim); mesh axes not present in `mesh` are dropped too
    (lets the same rules serve single-pod and multi-pod meshes).
    """
    used = set()
    out = []
    avail = set(mesh.axis_names) if mesh is not None else None
    for ax in axes:
        m = rules.mesh_axes(ax)
        if m is None:
            out.append(None)
            continue
        cand = (m,) if isinstance(m, str) else tuple(m)
        keep = tuple(a for a in cand
                     if a not in used and (avail is None or a in avail))
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    return P(*out)


def logical_sharding(axes: Sequence[Optional[str]], rules: LogicalRules,
                     mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(axes, rules, mesh))


def logical_spec_sized(shape: Sequence[int], axes: Sequence[Optional[str]],
                       rules: LogicalRules, mesh: Mesh) -> P:
    """Like `logical_spec` but drops mesh axes a dimension cannot divide.

    Example: a 50280-vocab can't shard 16 ways → the vocab dim falls back
    to replicated; whisper's 20 heads can't shard over model=16 → heads
    replicated (the arch then runs FSDP+DP only).
    For tuple assignments like ("pod","data") the prefix subsets are
    tried before giving up.
    """
    assert len(shape) == len(axes), (shape, axes)
    used = set()
    avail = dict(mesh.shape)
    out = []
    for dim, ax in zip(shape, axes):
        m = rules.mesh_axes(ax)
        if m is None:
            out.append(None)
            continue
        cand = (m,) if isinstance(m, str) else tuple(m)
        cand = tuple(a for a in cand if a in avail and a not in used)
        # try the longest divisible prefix
        chosen: Tuple[str, ...] = ()
        for k in range(len(cand), 0, -1):
            size = int(np.prod([avail[a] for a in cand[:k]]))
            if dim % size == 0:
                chosen = cand[:k]
                break
        used.update(chosen)
        if not chosen:
            out.append(None)
        elif len(chosen) == 1:
            out.append(chosen[0])
        else:
            out.append(chosen)
    return P(*out)


def shard_constraint(x: jax.Array, axes: Sequence[Optional[str]],
                     rules: Optional[LogicalRules],
                     mesh: Optional[Mesh] = None) -> jax.Array:
    """Activation sharding hint; no-op without rules/mesh context."""
    if rules is None:
        return x
    try:
        spec = logical_spec(axes, rules, mesh)
        if mesh is not None:
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no mesh context (e.g. single-device smoke)


# --------------------------------------------------------------------------
# ambient activation-sharding context (perf iteration #1, EXPERIMENTS §Perf)
#
# Without explicit activation constraints the SPMD partitioner replicated
# attention heads over the `model` axis (observed: per-device QK^T dots
# with the FULL head count — a ~16× compute inflation).  Model code calls
# `act_shard(x, *logical_axes)`; the launcher activates the context per
# step so smoke tests (no mesh) stay unaffected.
# --------------------------------------------------------------------------

import contextlib
import threading

_ctx = threading.local()


@contextlib.contextmanager
def sharding_ctx(rules: LogicalRules, mesh: Mesh):
    prev = getattr(_ctx, "val", None)
    _ctx.val = (rules, mesh)
    try:
        yield
    finally:
        _ctx.val = prev


def current_ctx():
    """(rules, mesh) of the ambient sharding context, or None."""
    return getattr(_ctx, "val", None)


def act_shard(x, *axes: Optional[str]):
    """Constrain an activation to its logical axes (ambient ctx; no-op
    outside a `sharding_ctx`).  Indivisible dims fall back gracefully."""
    ctx = getattr(_ctx, "val", None)
    if ctx is None:
        return x
    rules, mesh = ctx
    spec = logical_spec_sized(x.shape, axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Rule tables
# --------------------------------------------------------------------------

_FSDP = ("pod", "data")  # parameter / optimizer-state sharding axes

RULES_TRAIN = LogicalRules(
    name="train",
    rules=(
        # activations
        ("batch", _FSDP),
        ("seq", None),
        ("act_embed", None),
        ("act_heads", "model"),
        ("act_kv_heads", "model"),
        # batch_attn disabled in TRAIN: the per-layer resharding traffic
        # exceeds the compute win when training is collective-bound
        # (EXPERIMENTS §Perf iteration 6); decode/prefill keep it.
        ("batch_attn", None),
        ("act_mlp", "model"),
        ("act_expert", "model"),
        ("act_vocab", "model"),
        # parameters: tensor-parallel over model; FSDP over (pod, data)
        ("embed", _FSDP),          # d_model dim of params
        ("vocab", "model"),
        ("heads", "model"),
        ("kv_heads", "model"),
        ("head_dim", None),
        ("mlp", "model"),
        ("expert", "model"),       # expert-parallel
        ("expert_mlp", ("model", "data")),  # TP if expert dim could not take model (grok E=8), else FSDP
        ("layers", None),
        ("kv_lora", None),
        ("q_lora", None),
        ("state", None),
        ("conv", None),
        ("frontend", None),
    ),
)

RULES_DECODE = LogicalRules(
    name="decode",
    rules=(
        ("batch", _FSDP),
        ("seq", None),
        ("cache_seq", None),
        ("act_embed", None),
        ("act_heads", "model"),
        ("act_kv_heads", "model"),
        ("batch_attn", ("pod", "data", "model")),
        ("act_mlp", "model"),
        ("act_expert", "model"),
        ("act_vocab", "model"),
        ("embed", None),           # params replicated over data for serving,
        ("vocab", "model"),        # sharded over model only (weights are
        ("heads", "model"),        # read-only; FSDP gather every step would
        ("kv_heads", "model"),     # dominate decode)
        ("head_dim", None),
        ("mlp", "model"),
        ("expert", "model"),
        ("expert_mlp", None),
        ("layers", None),
        ("kv_lora", None),
        ("q_lora", None),
        ("state", None),
        ("conv", None),
        ("frontend", None),
    ),
)

# batch=1 long-context: shard the cache sequence dim over (pod, data)
RULES_LONG_DECODE = RULES_DECODE.replace(
    batch=None, cache_seq=_FSDP,
)
RULES_LONG_DECODE = dataclasses.replace(RULES_LONG_DECODE, name="long_decode")
