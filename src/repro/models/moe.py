"""Mixture-of-Experts layer with sort-based (dropping) token dispatch.

Expert-parallel design: experts shard over the ``model`` mesh axis; the
dispatch gather/scatter across the expert dimension is exactly the
paper's sparse-peer communication pattern — under pjit the
partitioner lowers it to all-to-all traffic on the expert axis, and the
ST benchmarks exercise the same pattern explicitly through
``overlap.all_to_all_ppermute``.  :func:`build_moe_dispatch_program`
expresses that exchange as a first-class ST program (one start gate of
staged trigger→wait channels, via
:mod:`repro.core.collectives`) so the dispatch composes/tunes/persists
with the rest of a step's schedule.

Routing flavours:
* ``softmax`` (grok-1): softmax over router logits, top-k, renormalized;
* ``sigmoid`` (deepseek-v3): sigmoid scores, top-k on score+bias
  (aux-free load balancing bias, a non-trained buffer), weights
  normalized over the selected experts and scaled by
  ``routed_scaling``.

Dispatch: tokens sort by expert id; each expert processes a fixed
capacity ``C = ceil(T·k/E · capacity_factor)`` (overflow drops — the
standard capacity model); gather → batched expert FFN → weighted
scatter-add.  All shapes static.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.parallel import act_shard, current_ctx
from .nn import Boxed, param


def init_moe(key, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p = {
        "router": param(ks[0], (d, e), ("embed", "act_expert"), dt, scale=0.006),
        "wi": param(ks[1], (e, d, f), ("expert", "embed", "expert_mlp"), dt),
        "wo": param(ks[3], (e, f, d), ("expert", "expert_mlp", "embed"), dt,
                    scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.act == "silu":
        p["wg"] = param(ks[2], (e, d, f), ("expert", "embed", "expert_mlp"), dt)
    if cfg.router == "sigmoid":
        # aux-free balancing bias — buffer, not a trained weight
        p["router_bias"] = param(ks[4], (e,), ("act_expert",), jnp.dtype("float32"),
                                 init="zeros")
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared_wi"] = param(ks[5], (d, fs), ("embed", "mlp"), dt)
        p["shared_wg"] = param(ks[5], (d, fs), ("embed", "mlp"), dt)
        p["shared_wo"] = param(ks[5], (fs, d), ("mlp", "embed"), dt,
                               scale=0.02 / math.sqrt(2 * max(cfg.n_layers, 1)))
    return p


def _route(p, x2d, cfg: ModelConfig):
    """x2d: [T, D] → (topk_idx [T,k], topk_w [T,k], router_probs [T,E])."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + p["router_bias"][None, :]
        _, idx = jax.lax.top_k(biased, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        w = w * cfg.routed_scaling
        probs = scores / (jnp.sum(scores, -1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w, probs


def _expert_ffn(p, xin, cfg: ModelConfig):
    """xin: [E, C, D] → [E, C, D] (batched per-expert FFN)."""
    dt = xin.dtype
    h = jnp.einsum("ecd,edf->ecf", xin, p["wi"].astype(dt))
    if "wg" in p:
        g = jnp.einsum("ecd,edf->ecf", xin, p["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))


def apply_moe_ep(p, x, cfg: ModelConfig) -> Optional[Tuple[jax.Array, Dict]]:
    """Expert-parallel MoE via shard_map (perf iteration 2, §Perf).

    The auto-partitioned gather dispatch lets tokens reach experts
    across *data* shards, which XLA lowers to whole-activation
    all-gathers per MoE layer (observed: ~6e13 wire bytes/device for
    deepseek-v3 train_4k).  This path instead keeps dispatch LOCAL:

    * activations stay sharded over (pod, data) and replicated over
      ``model`` (they already are, under tensor parallelism);
    * experts shard over ``model``; every (data, model) shard routes its
      own tokens to its own expert block — zero dispatch communication;
    * one ``psum`` over ``model`` combines expert contributions — the
      same collective a dense TP MLP needs anyway.

    Capacity is per data-shard (C_loc = ceil(T_loc·k/E·cf)): statistics
    differ slightly from the global-capacity gather path (drops are
    per-shard), which is the standard expert-parallel trade.

    Returns None when inapplicable (no mesh ctx / indivisible experts);
    caller falls back to the gather path.
    """
    ctx = current_ctx()
    if ctx is None:
        return None
    rules, mesh = ctx
    if "model" not in mesh.axis_names:
        return None
    m = mesh.shape["model"]
    E = cfg.n_experts
    # E ≥ m: E_loc experts per shard.  E < m (grok: 8 experts over a
    # 16-way axis): split each expert's FFN dim over r = m/E ranks
    # ("virtual experts" — elementwise nonlinearity keeps partial-F
    # outputs correct, and the combine psum sums the F-parts).
    if E % m != 0 and m % E != 0:
        return None
    B, S, D = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_b = 1
    for a in batch_axes:
        n_b *= mesh.shape[a]
    if B % n_b != 0:
        return None
    T_loc = (B // n_b) * S
    k = cfg.top_k
    E_loc = max(E // m, 1)
    n_rep = max(m // E, 1)
    F = cfg.d_ff_expert
    if F % n_rep != 0:
        return None
    C_loc = max(1, int(math.ceil(T_loc * k / E * cfg.capacity_factor)))

    def _virtualize_in(w):   # (E, D, F) → (E·r, D, F/r)
        if n_rep == 1:
            return w
        return w.reshape(E, D, n_rep, F // n_rep).transpose(0, 2, 1, 3) \
                .reshape(E * n_rep, D, F // n_rep)

    def _virtualize_out(w):  # (E, F, D) → (E·r, F/r, D)
        if n_rep == 1:
            return w
        return w.reshape(E, n_rep, F // n_rep, D).reshape(
            E * n_rep, F // n_rep, D)

    from jax.sharding import PartitionSpec as P

    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    x_spec = P(bspec, None, None)
    in_specs = (
        x_spec,
        P(None, None),                 # router (replicated)
        P("model", None, None),        # wi
        P("model", None, None),        # wg (or dummy)
        P("model", None, None),        # wo
        P(None),                       # router_bias
    )
    has_wg = "wg" in p

    def body(x_l, router, wi, wg_l, wo, rbias):
        Bl, Sl, _ = x_l.shape
        Tl = Bl * Sl
        x2 = x_l.reshape(Tl, D)
        pp = {"router": router, "router_bias": rbias}
        idx, w, probs = _route(pp, x2, cfg)

        # real-expert block of this rank (virtual-expert aware):
        # n_rep=1 → [rank·E_loc, …); n_rep>1 → {rank // n_rep}
        e0 = (jax.lax.axis_index("model") * E_loc) // n_rep
        flat_e = idx.reshape(Tl * k)
        flat_t = jnp.repeat(jnp.arange(Tl), k)
        flat_w = w.reshape(Tl * k)
        local_e = flat_e - e0
        mine = (local_e >= 0) & (local_e < E_loc)
        sort_key = jnp.where(mine, local_e, E_loc)      # strangers last
        order = jnp.argsort(sort_key, stable=True)
        se, st, sw = sort_key[order], flat_t[order], flat_w[order]
        counts = jnp.bincount(sort_key, length=E_loc + 1)[:E_loc]
        starts = jnp.cumsum(counts) - counts
        in_range = se < E_loc
        rank = jnp.arange(Tl * k) - starts[jnp.minimum(se, E_loc - 1)]
        keep = in_range & (rank < C_loc)
        slot = jnp.minimum(se, E_loc - 1) * C_loc + jnp.where(keep, rank, 0)
        slot_scatter = jnp.where(keep, slot, E_loc * C_loc)

        x_pad = jnp.concatenate([x2, jnp.zeros((1, D), x2.dtype)], axis=0)
        dispatch = jnp.full((E_loc * C_loc + 1,), Tl, dtype=jnp.int32).at[
            slot_scatter].set(jnp.where(keep, st, Tl))[:E_loc * C_loc]
        xin = x_pad[dispatch].reshape(E_loc, C_loc, D)

        dt = xin.dtype
        h = jnp.einsum("ecd,edf->ecf", xin, wi.astype(dt))
        if has_wg:
            g = jnp.einsum("ecd,edf->ecf", xin, wg_l.astype(dt))
            h = jax.nn.silu(g) * h
        else:
            h = jax.nn.gelu(h)
        yout = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))

        y_flat = yout.reshape(E_loc * C_loc, D)[slot]
        contrib = y_flat * (sw * keep).astype(y_flat.dtype)[:, None]
        y2 = jax.ops.segment_sum(contrib, st, num_segments=Tl)
        y2 = jax.lax.psum(y2, "model")                  # combine experts

        # balance stats (identical across model shards pre-psum; average
        # the drop/balance metrics over the data shards)
        frac_tokens = jnp.mean(
            (jax.nn.one_hot(idx, E).sum(1) > 0).astype(jnp.float32), axis=0)
        frac_probs = jnp.mean(probs, axis=0)
        lb = E * jnp.sum(frac_tokens * frac_probs)
        kept_mine = jnp.sum(keep.astype(jnp.float32))
        total_mine = jnp.sum(mine.astype(jnp.float32))
        dropped = 1.0 - kept_mine / jnp.maximum(total_mine, 1.0)
        dropped = jax.lax.pmean(jax.lax.pmean(dropped, "model"),
                                batch_axes) if batch_axes else dropped
        if batch_axes:
            lb = jax.lax.pmean(lb, batch_axes)
            frac_probs = jax.lax.pmean(frac_probs, batch_axes)
        return (y2.reshape(Bl, Sl, D).astype(x_l.dtype), lb, frac_probs,
                dropped)

    out_specs = (x_spec, P(), P(None), P())
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    rbias = p.get("router_bias", jnp.zeros((E,), jnp.float32))
    wi_v = _virtualize_in(p["wi"])
    wg_v = _virtualize_in(p["wg"]) if has_wg else wi_v
    wo_v = _virtualize_out(p["wo"])
    y, lb, frac_probs, dropped = sm(x, p["router"], wi_v, wg_v, wo_v, rbias)

    if "shared_wi" in p:
        dt = x.dtype
        h = act_shard(jnp.einsum("bsd,df->bsf", x, p["shared_wi"].astype(dt)),
                      "batch", "seq", "act_mlp")
        g = act_shard(jnp.einsum("bsd,df->bsf", x, p["shared_wg"].astype(dt)),
                      "batch", "seq", "act_mlp")
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h,
                           p["shared_wo"].astype(dt))
    aux = {"lb_loss": lb, "router_probs_mean": frac_probs,
           "dropped_frac": dropped}
    return y, aux


def build_moe_dispatch_program(mesh, axis: str, n_experts: int,
                               capacity: int, d_model: int,
                               dtype=jnp.float32, *, verify: str = "warn",
                               name: str = "st_moe_dispatch"):
    """MoE all-to-all dispatch as a composable ST program.

    The expert-parallel dispatch exchange — every rank's sorted
    capacity buffer ``[E, C, D]`` (flattened to ``E*C`` rows, experts
    contiguous) sent so the block for expert ``e`` lands on the rank
    owning it — is exactly a tiled all-to-all over the expert rows.
    This builder expresses it through
    :func:`repro.core.collectives.build_all_to_all`: one start gate of
    n-1 staged trigger→wait channels, so the dispatch coalesces,
    STLints, prices under ``schedule_cost``, composes with other
    queues (expert FFN kernels ride inside the gate's trigger→wait
    window), and runs persistent.  Bit-identical to
    ``overlap.all_to_all_ppermute`` and ``lax.all_to_all`` (pure
    copies).

    The combine leg is the same exchange in reverse — the tiled a2a is
    an involution, so running the returned program a second time (or
    ``.persistent(2)``) routes expert outputs back to their source
    ranks.

    Returns a :class:`repro.core.collectives.CollectiveMatmul` whose
    ``inputs`` / ``output`` buffers are the flattened dispatch rows.
    """
    from repro.core import collectives

    n = dict(mesh.shape)[axis]
    if n_experts % n:
        raise ValueError(
            f"n_experts ({n_experts}) must divide by the {axis!r} axis "
            f"size ({n}) for expert-parallel dispatch")
    rows = n * n_experts * capacity  # global: every rank holds E*C rows
    return collectives.build_all_to_all(mesh, axis, rows, d_model, dtype,
                                        verify=verify, name=name)


def apply_moe(p, x, cfg: ModelConfig, *, capacity: Optional[int] = None
              ) -> Tuple[jax.Array, Dict]:
    """x: [B, S, D] → (y, aux) with aux = {"lb_loss", "router_probs_mean"}."""
    if cfg.moe_impl == "ep" and capacity is None:
        out = apply_moe_ep(p, x, cfg)
        if out is not None:
            return out
    B, S, D = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.top_k
    x2d = x.reshape(T, D)
    idx, w, probs = _route(p, x2d, cfg)

    if capacity is None:
        capacity = max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))
    C = capacity

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = idx.reshape(T * k)                       # expert of each assignment
    flat_t = jnp.repeat(jnp.arange(T), k)             # token of each assignment
    flat_w = w.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts              # [E]
    rank = jnp.arange(T * k) - starts[se]             # slot within expert
    keep = rank < C
    slot = se * C + jnp.where(keep, rank, 0)          # [T*k] (clamped)

    # gather tokens into expert buffers (padded with a zero row).
    # Dropped assignments scatter into a trash slot (index E*C) so they
    # can never clobber a kept entry's slot.
    x_pad = jnp.concatenate([x2d, jnp.zeros((1, D), x2d.dtype)], axis=0)
    slot_scatter = jnp.where(keep, slot, E * C)
    dispatch = jnp.full((E * C + 1,), T, dtype=jnp.int32).at[
        slot_scatter].set(jnp.where(keep, st, T))[:E * C]
    xin = act_shard(x_pad[dispatch].reshape(E, C, D), "act_expert", None, None)

    yout = act_shard(_expert_ffn(p, xin, cfg), "act_expert", None, None)

    # combine: weighted scatter-add back to tokens
    y_flat = yout.reshape(E * C, D)[slot]             # per-assignment output
    contrib = y_flat * (sw * keep).astype(y_flat.dtype)[:, None]
    y2d = jax.ops.segment_sum(contrib, st, num_segments=T)
    y = y2d.reshape(B, S, D).astype(x.dtype)

    # shared experts (dense path, always on)
    if "shared_wi" in p:
        dt = x.dtype
        h = act_shard(jnp.einsum("bsd,df->bsf", x, p["shared_wi"].astype(dt)),
                      "batch", "seq", "act_mlp")
        g = act_shard(jnp.einsum("bsd,df->bsf", x, p["shared_wg"].astype(dt)),
                      "batch", "seq", "act_mlp")
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h,
                           p["shared_wo"].astype(dt))

    # load-balance loss (Switch-style; deepseek uses the bias instead but
    # we report it for monitoring either way)
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(idx, E).sum(1) > 0).astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    aux = {"lb_loss": lb_loss, "router_probs_mean": frac_probs,
           "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return y, aux
