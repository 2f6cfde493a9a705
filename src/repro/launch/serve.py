"""Serving driver: device-resident continuous-batching decode.

The serving analogue of the repo's stream-triggered offload thesis: the
greedy-decode control loop — the part the legacy driver host-stepped one
token-dispatch at a time — runs **device-resident** as one
``lax.while_loop`` dispatch with per-sequence EOS / max-len termination
(masked per sequence exactly like the composed scheduler's per-program
``n_done`` in :mod:`repro.core.engine_persistent`), and **continuous
batching** admits new requests into freed KV-cache slots between
dispatches.  Admission itself is a *composed* prefill+decode program:
one dispatch prefills the admitted slots (into a zeroed view, merged
per-slot via :meth:`repro.models.Model.select_slots`) and then resumes
the in-flight decode loop — prefill of incoming requests overlaps
in-flight decode inside ONE dispatch, the launch-layer analogue of
:func:`repro.core.schedule.compose`.  KV-cache slots are recycled
zero-copy: the jitted dispatches donate the cache/state buffers
(PR-4's ``(cur, alt)`` rotation applied to the serve chain — the
``caches = step(caches, ...)`` loop rotates buffers without copies; the
donated input is deleted).

CLI
---
``PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke
--batch 4 --prompt-len 32 --gen 16 [--mesh DxM] [--serve-window W]
[--seed S] [--eos-id K] [--host-stepped] [--requests N --rate R
--chunk C]``

* ``--serve-window`` — windowed-attention serving cap (0 = off),
  threaded to prefill and decode steps.
* ``--seed`` — RNG seed for params and synthetic prompts.
* ``--host-stepped`` — legacy one-dispatch-per-token loop (baseline).
* ``--requests/--rate/--chunk`` — continuous-batching mode: N synthetic
  requests arriving as a Poisson process at R req/s (0 = all at t=0),
  decode chunked every C tokens between admission points.

BENCH_serve.json schema (written by ``benchmarks/serve_bench.py``, gated
by ``benchmarks/run.py serve --check-against BENCH_serve.json``)::

  {
    "serve/<variant>": {            # host_stepped | resident | continuous
      "tok_per_s": float,           # tokens emitted / serve wall-clock s
      "median_ms": float,           # median serve wall-clock over repeats
      "dispatches": int,            # host dispatches for the request set
      "p50_ms": float, "p99_ms": float,   # per-request latency percentiles
    },
    "_meta": { ... }                # workload stamp: medians only compare
  }                                 # like-for-like (cf. BENCH_faces.json)
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.base import ModelConfig, ShapeConfig, get_config
from repro.launch.steps import build_prefill_step, build_serve_step
from repro.models import Model
from repro.parallel import sharding_ctx

#: emission marker for a slot that was not active at a given decode step
PAD_TOKEN = -1


class _Counted:
    """Wrap a jitted callable and count host dispatches through it."""

    def __init__(self, fn):
        self._fn = fn
        self.calls = 0

    def __call__(self, *args):
        self.calls += 1
        return self._fn(*args)


def _argmax_tok(logits):
    return jnp.argmax(logits, -1).astype(jnp.int32)


def build_admission_schedule(mesh=None, *, slots: int = 4, width: int = 8,
                             verify: str = "error"):
    """The admission composition as an explicit ST schedule.

    :meth:`ServeEngine._admit_decode_inner` fuses "prefill the admitted
    slots, then resume in-flight decode" into one dispatch, but it does
    so as a plain jitted function — opaque to STLint.  This builder
    expresses the same handoff as two :class:`~repro.core.STQueue`
    programs joined by a cross-program link, so the admission path has a
    lintable model: ``prefill`` computes the KV for the admitted slots
    and *sends* it; ``decode`` *receives* it into its cache slot, waits
    on the deposit (triggered-op semantics: the decode step must not
    read the slot before the prefill deposit lands), then steps.  The
    ``python -m repro.analysis`` CLI and the verifier test sweep lint
    this schedule alongside the faces programs.
    """
    from repro.core import OffsetPeer, STQueue, compose

    if mesh is None:
        from repro.parallel import make_mesh
        mesh = make_mesh((jax.device_count(),), ("x",))
    ax = mesh.axis_names[0]
    n = int(mesh.shape[ax]) * slots

    qp = STQueue(mesh, name="prefill")
    qp.buffer("prompt", (n, width), np.float32, pspec=(ax, None))
    qp.buffer("kv", (n, width), np.float32, pspec=(ax, None))
    qp.enqueue_kernel(jnp.tanh, ["prompt"], ["kv"], name="prefill")
    qp.enqueue_send("kv", OffsetPeer(ax, 0, periodic=True), tag=31,
                    remote="decode")
    qp.enqueue_start()
    qp.enqueue_wait()
    prefill = qp.build()

    qd = STQueue(mesh, name="decode")
    qd.buffer("cache", (n, width), np.float32, pspec=(ax, None))
    qd.buffer("tok", (n, width), np.float32, pspec=(ax, None))
    qd.enqueue_recv("cache", OffsetPeer(ax, 0, periodic=True), tag=31,
                    remote="prefill")
    qd.enqueue_start()
    qd.enqueue_wait()
    qd.enqueue_kernel(lambda c: jnp.cumsum(c, axis=-1), ["cache"], ["tok"],
                      name="decode")
    decode = qd.build()

    return compose(prefill, decode, name="serve_admission", verify=verify)


class ServeEngine:
    """Jit-compiled serve programs over one slot-set of KV caches.

    Three dispatch kinds, all sharing the same per-sequence decode-loop
    core (``chunk`` steps, masked per slot):

    * ``prefill(params, batch_in, caches)`` — the jitted prefill step
      (cache shardings rebuilt against the decode bundle's max-len
      caches, as the legacy driver only promised in a comment);
    * ``decode(params, caches, tok, active, rem)`` — device-resident
      greedy decode: up to ``chunk`` tokens for every active slot in ONE
      dispatch, stopping each slot at EOS / budget / cache capacity;
    * ``admit_decode(params, caches, tok, active, rem, batch_in, admit,
      new_rem)`` — the composed prefill+decode program: masked prefill
      of the admitted slots overlapping the in-flight decode loop, still
      ONE dispatch.

    All decode-state arguments are donated: the serve chain rotates the
    cache buffers zero-copy across dispatches (the donated inputs are
    deleted — PR-4 slot rotation at the serve layer).
    """

    def __init__(self, cfg: ModelConfig, mesh, *, slots: int,
                 prompt_len: int, max_new: int, chunk: Optional[int] = None,
                 eos_id: int = -1, serve_window: int = 0,
                 donate: bool = True):
        self.cfg, self.mesh = cfg, mesh
        self.slots, self.prompt_len, self.max_new = slots, prompt_len, max_new
        self.eos_id, self.serve_window = int(eos_id), serve_window
        self.model = Model(cfg)
        self.prefix_len = self.model._prefix_len()
        self.capacity = self.prefix_len + prompt_len + max_new
        self.chunk = int(chunk) if chunk else max(max_new - 1, 1)
        self.sync_points = 0

        pre_shape = ShapeConfig("serve_prefill", prompt_len, slots, "prefill")
        dec_shape = ShapeConfig("serve_decode", self.capacity, slots, "decode")
        self.pre = build_prefill_step(cfg, pre_shape, mesh,
                                      serve_window=serve_window)
        self.dec = build_serve_step(cfg, dec_shape, mesh,
                                    serve_window=serve_window,
                                    per_seq_pos=True)
        self.cache_shardings = self.dec.in_shardings[1]

        with mesh:
            # satellite bugfix: the prefill step is actually jitted and
            # executed — with its cache shardings rebuilt against the
            # decode bundle's max-len caches (serving shares ONE cache
            # set sized to capacity; the prefill bundle's own caches_sd
            # is sized prompt_len+prefix and must not win).
            self.prefill = _Counted(jax.jit(
                self.pre.step_fn,
                in_shardings=(self.pre.in_shardings[0],
                              self.pre.in_shardings[1],
                              self.cache_shardings),
                out_shardings=(self.pre.out_shardings[0],
                               self.cache_shardings)))
            donate_state = (1, 2, 3, 4) if donate else ()
            self.decode = _Counted(jax.jit(
                self._decode_fn, donate_argnums=donate_state))
            self.admit_decode = _Counted(jax.jit(
                self._admit_decode_fn, donate_argnums=donate_state))
            # legacy-shaped single-token step for the host-stepped
            # baseline (donates caches, like the old driver)
            self.decode_one = _Counted(jax.jit(
                self.dec.step_fn, in_shardings=self.dec.in_shardings,
                out_shardings=self.dec.out_shardings, donate_argnums=(1,)))

    # -- state ----------------------------------------------------------------

    def init_state(self):
        """(caches, tok, active, rem) — all slots free.  Placed with the
        decode bundle's shardings."""
        caches = self.model.init_caches(self.slots, self.capacity,
                                        per_sequence=True)
        caches = jax.device_put(caches, self.cache_shardings)
        tok = jnp.zeros((self.slots,), jnp.int32)
        active = jnp.zeros((self.slots,), bool)
        rem = jnp.zeros((self.slots,), jnp.int32)
        return caches, tok, active, rem

    @property
    def dispatches(self) -> int:
        return (self.prefill.calls + self.decode.calls
                + self.admit_decode.calls + self.decode_one.calls)

    # -- device-resident decode loop core -------------------------------------

    def _decode_loop(self, params, caches, tok, active, rem):
        """Up to ``chunk`` greedy-decode steps as ONE on-device loop.

        Per-sequence masking mirrors the composed scheduler's per-program
        ``n_done``: a finished slot's position freezes (its K/V writes
        land on the frozen next-free index, invisible behind the
        ``k_valid`` mask), its emissions pad, and the loop ends when
        every slot is done or the chunk budget is spent.  Termination
        per slot: EOS (``eos_id >= 0``), per-slot token budget ``rem``,
        or cache capacity (max-len).
        """
        B, chunk, eos = self.slots, self.chunk, self.eos_id
        out0 = jnp.full((B, chunk), PAD_TOKEN, jnp.int32)
        n0 = jnp.zeros((B,), jnp.int32)

        def cond(c):
            i, _, _, active, _, _, _ = c
            return jnp.logical_and(i < chunk, jnp.any(active))

        def body(c):
            i, caches, tok, active, rem, out, n = c
            logits, new_caches = self.model.decode_step(
                params, caches, tok, serve_window=self.serve_window)
            nxt = _argmax_tok(logits)
            emit = jnp.where(active, nxt, PAD_TOKEN)
            out = jax.lax.dynamic_update_index_in_dim(out, emit, i, axis=1)
            n = n + active.astype(jnp.int32)
            # a frozen slot's depth does not advance (its discarded
            # write lands at the frozen next-free index each pass)
            pos = jnp.where(active, new_caches["pos"], caches["pos"])
            new_caches = dict(new_caches)
            new_caches["pos"] = pos
            rem = rem - active.astype(jnp.int32)
            stop = rem <= 0
            if eos >= 0:
                stop = stop | (nxt == eos)
            stop = stop | (pos >= self.capacity)
            active = active & ~stop
            tok = jnp.where(active, nxt, tok)
            return i + 1, new_caches, tok, active, rem, out, n

        _, caches, tok, active, rem, out, n = jax.lax.while_loop(
            cond, body,
            (jnp.zeros((), jnp.int32), caches, tok, active, rem, out0, n0))
        return caches, tok, active, rem, out, n

    def _decode_fn(self, params, caches, tok, active, rem):
        with sharding_ctx(self.dec.rules, self.mesh):
            return self._decode_loop(params, caches, tok, active, rem)

    # -- composed prefill + decode (continuous-batching admission) -------------

    def _admit_decode_fn(self, params, caches, tok, active, rem,
                         batch_in, admit, new_rem):
        """ONE dispatch: masked prefill of the admitted slots, then the
        in-flight decode loop resumes over ALL active slots.

        Prefill runs against a zeroed cache view (a recycled slot's
        stale K/V and SSM state must not leak into the new request) at
        per-slot depth 0, and only the admitted slots take the prefilled
        values (:meth:`Model.select_slots`); everyone else's mid-flight
        state is untouched.  The prefill-produced token is the admitted
        slot's first emission and its first decode input.
        """
        with sharding_ctx(self.dec.rules, self.mesh):
            return self._admit_decode_inner(params, caches, tok, active,
                                            rem, batch_in, admit, new_rem)

    def _admit_decode_inner(self, params, caches, tok, active, rem,
                            batch_in, admit, new_rem):
        zero = jax.tree.map(jnp.zeros_like, caches)
        logits, pre = self.model.prefill(
            params, batch_in, zero, serve_window=self.serve_window)
        caches = self.model.select_slots(admit, pre, caches)
        tok0 = _argmax_tok(logits)
        first = jnp.where(admit, tok0, PAD_TOKEN)
        tok = jnp.where(admit, tok0, tok)
        # the prefill token is emission #1 of the admitted request
        rem_admitted = new_rem - 1
        fresh = admit
        stop = rem_admitted <= 0
        if self.eos_id >= 0:
            stop = stop | (tok0 == self.eos_id)
        stop = stop | (caches["pos"] >= self.capacity)
        fresh = fresh & ~stop
        active = jnp.where(admit, fresh, active)
        rem = jnp.where(admit, rem_admitted, rem)
        caches, tok, active, rem, out, n = self._decode_loop(
            params, caches, tok, active, rem)
        return caches, tok, active, rem, first, out, n


# --------------------------------------------------------------------------
# synthetic workload
# --------------------------------------------------------------------------


def synthetic_batch(cfg: ModelConfig, rng, batch: int, prompt_len: int):
    """Synthetic prompt batch (tokens + any frontend embeddings)."""
    out = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, prompt_len)).astype(np.int32))}
    if cfg.enc_dec:
        out["audio_embeds"] = jnp.asarray(
            rng.randn(batch, cfg.frontend_tokens, cfg.frontend_dim),
            jnp.float32)
    if cfg.frontend == "vision":
        out["vision_embeds"] = jnp.asarray(
            rng.randn(batch, cfg.frontend_tokens, cfg.frontend_dim),
            jnp.float32)
    return out


# --------------------------------------------------------------------------
# single-shot serving (one fixed batch, everyone starts together)
# --------------------------------------------------------------------------


def serve(cfg: ModelConfig, mesh, *, batch: int, prompt_len: int,
          gen_len: int, seed: int = 0, serve_window: int = 0,
          eos_id: int = -1, device_resident: bool = True,
          params=None, batch_in=None,
          engine: Optional[ServeEngine] = None):
    """Batched prefill + greedy decode for one fixed batch.

    ``device_resident=True`` (default): the whole decode loop runs as
    ONE host dispatch (``stats["decode_dispatches"] == 1``).  False:
    the legacy host-stepped loop — one dispatch per token — kept as the
    measured baseline and bit-identity reference.

    Returns ``(gen, stats)``: ``gen`` is ``[batch, gen_len]`` int32 —
    column 0 is the prefill-produced token — with ``PAD_TOKEN`` (-1)
    past a sequence's EOS.  ``stats`` counts actual emitted decode
    tokens (early-EOS sequences emit fewer) and syncs once at the end,
    so ``tok_per_s = decode_tokens / decode_s`` is consistent.
    """
    eng = engine or ServeEngine(
        cfg, mesh, slots=batch, prompt_len=prompt_len, max_new=gen_len,
        chunk=gen_len - 1, eos_id=eos_id, serve_window=serve_window)
    assert (eng.slots == batch and eng.chunk == gen_len - 1
            and eng.eos_id == int(eos_id)), "engine/serve shape mismatch"
    base_disp = eng.dispatches
    base_dec = eng.decode.calls + eng.decode_one.calls
    with mesh:
        if params is None:
            params, _ = eng.model.init(jax.random.PRNGKey(seed))
        params = jax.device_put(params, eng.pre.in_shardings[0])
        rng = np.random.RandomState(seed)
        if batch_in is None:
            batch_in = synthetic_batch(cfg, rng, batch, prompt_len)
        caches, tok, active, rem = eng.init_state()

        t0 = time.time()
        logits, caches = eng.prefill(params, batch_in, caches)
        tok0 = _argmax_tok(logits)
        tok0_np = np.asarray(tok0)   # prefill sync point (tok0 is later donated)
        t_prefill = time.time() - t0

        active = jnp.ones((batch,), bool)
        rem = jnp.full((batch,), gen_len - 1, jnp.int32)
        if eos_id >= 0:
            active = active & (tok0 != eos_id)

        t0 = time.time()
        if device_resident:
            caches, tok, active, rem, out, n_emit = eng.decode(
                params, caches, tok0, active, rem)
            out = np.asarray(out)
            n_np = np.asarray(n_emit)
            eng.sync_points += 1
        else:
            # legacy host-stepped loop (fixed accounting: no per-step
            # host sync — emissions stay on device until the end)
            emitted = []
            cur = tok0
            for _ in range(gen_len - 1):
                logits, caches = eng.decode_one(params, caches, cur)
                cur = _argmax_tok(logits)
                emitted.append(cur)
            jax.block_until_ready(cur)
            eng.sync_points += 1
            out = np.stack([np.asarray(t) for t in emitted], axis=1)
            # host-side EOS truncation (the oracle the resident loop's
            # on-device masking must reproduce exactly)
            if eos_id >= 0:
                for b in range(batch):
                    stop = gen_len - 1 if tok0_np[b] != eos_id else 0
                    hits = np.nonzero(out[b] == eos_id)[0]
                    if hits.size:
                        stop = min(stop, hits[0] + 1)
                    out[b, stop:] = PAD_TOKEN
            n_np = (out != PAD_TOKEN).sum(axis=1)
        t_decode = time.time() - t0

    gen = np.concatenate([tok0_np[:, None], out], axis=1)
    decode_tokens = int(n_np.sum())
    stats = {
        "prefill_s": t_prefill, "decode_s": t_decode,
        "decode_tokens": decode_tokens,
        "tok_per_s": decode_tokens / max(t_decode, 1e-9),
        "dispatches": eng.dispatches - base_disp,
        "decode_dispatches": eng.decode.calls + eng.decode_one.calls - base_dec,
        "sync_points": eng.sync_points,
    }
    return gen, stats


# --------------------------------------------------------------------------
# continuous batching (open-loop arrival stream)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RequestResult:
    rid: int
    tokens: np.ndarray        # emitted tokens (prefill token first)
    t_arrive: float
    t_done: float

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_arrive


def poisson_arrivals(n: int, rate: float, rng) -> np.ndarray:
    """Arrival offsets (s) for an open-loop Poisson stream; rate<=0 → a
    t=0 burst."""
    if rate <= 0:
        return np.zeros(n)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def serve_continuous(cfg: ModelConfig, mesh, *, slots: int, prompt_len: int,
                     max_new: int, n_requests: int, chunk: int = 4,
                     arrival_rate: float = 0.0, seed: int = 0,
                     eos_id: int = -1, serve_window: int = 0,
                     params=None, prompts=None,
                     engine: Optional[ServeEngine] = None):
    """Continuous-batching serve of an open-loop arrival stream.

    ``n_requests`` synthetic requests arrive as a Poisson process
    (``arrival_rate`` req/s; 0 → all at t=0) and are admitted into freed
    KV-cache slots between dispatches.  Each round is ONE dispatch —
    the composed prefill+decode program when any slot was admitted, the
    pure resident decode chunk otherwise — followed by exactly one host
    sync (the admission point).  Slots are recycled zero-copy (donated
    buffers rotate through the dispatch chain).

    Returns ``(results, stats)`` — per-request
    :class:`RequestResult` (tokens are bit-identical to serving the
    request alone) and aggregate stats (tok/s, p50/p99 latency,
    dispatch/sync counts).
    """
    eng = engine or ServeEngine(
        cfg, mesh, slots=slots, prompt_len=prompt_len, max_new=max_new,
        chunk=chunk, eos_id=eos_id, serve_window=serve_window)
    assert (eng.slots == slots and eng.prompt_len == prompt_len
            and eng.max_new >= max_new
            and eng.eos_id == int(eos_id)), "engine/serve shape mismatch"
    rng = np.random.RandomState(seed)
    with mesh:
        if params is None:
            params, _ = eng.model.init(jax.random.PRNGKey(seed))
        params = jax.device_put(params, eng.pre.in_shardings[0])
        all_prompts = (synthetic_batch(cfg, rng, n_requests, prompt_len)
                       if prompts is None else prompts)
        arrivals = poisson_arrivals(n_requests, arrival_rate,
                                    np.random.RandomState(seed + 1))

        caches, tok, active, rem = eng.init_state()
        slot_req = np.full(slots, -1)          # request id per slot
        emitted: List[List[int]] = [[] for _ in range(n_requests)]
        results: List[Optional[RequestResult]] = [None] * n_requests
        next_req = 0
        n_done = 0
        base_prefill = eng.prefill.calls
        base_admit = eng.admit_decode.calls
        base_decode = eng.decode.calls
        base_disp = eng.dispatches
        t0 = time.time()

        while n_done < n_requests:
            now = time.time() - t0
            free = [s for s in range(slots) if slot_req[s] < 0]
            admit_ids: List[Tuple[int, int]] = []   # (slot, rid)
            while free and next_req < n_requests and arrivals[next_req] <= now:
                admit_ids.append((free.pop(0), next_req))
                next_req += 1
            if not admit_ids and not (slot_req >= 0).any():
                # idle: nothing in flight, nothing arrived yet
                time.sleep(min(max(arrivals[next_req] - now, 0.0), 0.01))
                continue

            if admit_ids:
                admit_np = np.zeros(slots, bool)
                new_rem = np.zeros(slots, np.int32)
                rows = {k: np.asarray(v) for k, v in all_prompts.items()}
                batch_rows = {k: np.zeros((slots,) + v.shape[1:], v.dtype)
                              for k, v in rows.items()}
                for s, rid in admit_ids:
                    admit_np[s] = True
                    new_rem[s] = max_new
                    slot_req[s] = rid
                    for k in rows:
                        batch_rows[k][s] = rows[k][rid]
                batch_in = {k: jnp.asarray(v) for k, v in batch_rows.items()}
                caches, tok, active, rem, first, out, n_emit = eng.admit_decode(
                    params, caches, tok, active, rem, batch_in,
                    jnp.asarray(admit_np), jnp.asarray(new_rem))
            else:
                caches, tok, active, rem, out, n_emit = eng.decode(
                    params, caches, tok, active, rem)
                first = None

            # ONE host sync per round: the admission point
            out_np = np.asarray(out)
            act_np = np.asarray(active)
            first_np = np.asarray(first) if first is not None else None
            eng.sync_points += 1
            t_round = time.time() - t0

            for s in range(slots):
                rid = slot_req[s]
                if rid < 0:
                    continue
                if first_np is not None and first_np[s] != PAD_TOKEN:
                    emitted[rid].append(int(first_np[s]))
                emitted[rid].extend(
                    int(t) for t in out_np[s] if t != PAD_TOKEN)
                if not act_np[s]:
                    results[rid] = RequestResult(
                        rid=rid, tokens=np.asarray(emitted[rid], np.int32),
                        t_arrive=float(arrivals[rid]), t_done=t_round)
                    slot_req[s] = -1
                    n_done += 1

        t_total = time.time() - t0
    lat = np.asarray([r.latency_s for r in results])
    total_tokens = int(sum(len(e) for e in emitted))
    stats = {
        "total_s": t_total,
        "total_tokens": total_tokens,
        "tok_per_s": total_tokens / max(t_total, 1e-9),
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "dispatches": eng.dispatches - base_disp,
        "admit_dispatches": eng.admit_decode.calls - base_admit,
        "decode_dispatches": eng.decode.calls - base_decode,
        "prefill_dispatches": eng.prefill.calls - base_prefill,
        "sync_points": eng.sync_points,
    }
    return results, stats


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--serve-window", type=int, default=0,
                    help="windowed-attention serving cap (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--host-stepped", action="store_true",
                    help="legacy one-dispatch-per-token decode loop")
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous-batching mode: serve N requests")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = t=0 burst")
    ap.add_argument("--chunk", type=int, default=4,
                    help="decode chunk between admission points")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    dm, tm = (int(x) for x in args.mesh.split("x"))
    from repro.parallel import make_mesh
    mesh = make_mesh((dm, tm), ("data", "model"))

    if args.requests:
        results, stats = serve_continuous(
            cfg, mesh, slots=args.batch, prompt_len=args.prompt_len,
            max_new=args.gen, n_requests=args.requests, chunk=args.chunk,
            arrival_rate=args.rate, seed=args.seed, eos_id=args.eos_id,
            serve_window=args.serve_window)
        print(f"served {len(results)} requests "
              f"({stats['total_tokens']} tokens)")
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in stats.items()})
        return

    gen, stats = serve(cfg, mesh, batch=args.batch,
                       prompt_len=args.prompt_len, gen_len=args.gen,
                       seed=args.seed, serve_window=args.serve_window,
                       eos_id=args.eos_id,
                       device_resident=not args.host_stepped)
    print("generated tokens (first row):", gen[0][:16])
    print({k: round(v, 4) if isinstance(v, float) else v
           for k, v in stats.items()})


if __name__ == "__main__":
    main()
