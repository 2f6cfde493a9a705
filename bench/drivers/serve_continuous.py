"""Serving traffic: an open-loop stream of requests through
``serve_continuous`` on a ``ServeEngine`` built in set-up.

Every request has a prompt of ``prompt_len`` tokens drawn from the seed
and asks for ``max_new`` greedy tokens (no end-of-sequence token, so
every request does the same work).  Arrivals are a Poisson process at
``rate`` requests per second from ``arrival_seed``, the same in every
run; the run serves ``round(rate * seconds)`` requests.

The benchmark times the requests itself.  It stands in for each of the
engine's dispatch callables and records, on the host clock, when each
call was made and when its results were ready.  The program returns, per
request, its tokens and when it was stamped done (seconds after its own
start, at the host sync after the dispatch that gave its last token).
:func:`attribute` finds the one start of the program's clock that puts
every such stamp between a dispatch's results and the next call, and
from it the dispatch that finished each request: a request is done when
that dispatch's results were ready, and its latency counts from the time
it was due.  Nothing here depends on how the program assigns slots or on
what its dispatches return.

Parameters (``bench/traffic/<mix>.json``): ``prompt_len``, ``max_new``,
``slots``, ``chunk``, ``rate``, ``arrival_seed``, ``check_requests``
(how many finished requests, drawn from the seed, the reference checks)
and ``limits`` of the numbers compared with the reference.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import time
from typing import Any, List, Sequence, Tuple

import jax
import numpy as np

from bench import counts
from bench.harness import Outcome, check_le, phase, seed32


def arrivals(n: int, rate: float, seed: int) -> np.ndarray:
    """Arrival offsets (s) of an open-loop Poisson stream."""
    return np.cumsum(np.random.RandomState(seed).exponential(1.0 / rate, n))


@dataclasses.dataclass
class Call:
    name: str           # the engine's dispatch callable
    t0: float           # host clock (perf_counter) when the call was made
    t_ready: float      # host clock when its results were ready
    wall0: float        # the same two moments on the wall clock, which
    wall_ready: float   # the program stamps its requests with
    work: Any = None    # (admitted-slot mask or None, decode steps per slot)


def _work(args, out):
    """What one dispatch did, for the per-layer readers only: the slots
    it admitted (the ``admit`` argument of ``admit_decode(params, caches,
    tok, active, rem, batch_in, admit, new_rem)``; None for ``decode``)
    and the decode steps each slot ran (the last result of either)."""
    return (args[6] if len(args) == 8 else None), out[-1]


class _Timed:
    """Stands in for one of the engine's dispatch callables: host spans
    around each call and around the wait for its results, and the times
    of both.  The serving loop reads every call's results to the host
    right after making it, so waiting for them here moves that wait, not
    the device's work."""

    def __init__(self, fn, name: str, calls: List[Call]):
        self._fn, self._name, self._calls = fn, name, calls
        self.spans = None

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, *args):
        if self.spans is not None:
            self.spans.boundary()
        span = self.spans or (lambda name: contextlib.nullcontext())
        t0, wall0 = time.perf_counter(), time.time()
        with span(f"bench.enqueue.{self._name}"):
            out = self._fn(*args)
        with span(f"bench.wait.{self._name}"):
            jax.block_until_ready(out)
        t_ready, wall_ready = time.perf_counter(), time.time()
        try:
            work = _work(args, out)
        except (IndexError, TypeError):
            work = None
        self._calls.append(Call(self._name, t0, t_ready, wall0, wall_ready,
                                work))
        return out


def dispatch_work(calls: Sequence[Call], prompt_len: int, slots: int):
    """Per dispatch, what the per-layer readers count: its kind, loop
    steps, valid KV entries attended over, slots admitted and tokens
    decoded; None where a dispatch's work was not recorded."""
    if any(c.work is None for c in calls):
        return None
    masks = [np.zeros(slots, bool) if c.work[0] is None
             else np.asarray(c.work[0]) for c in calls]
    steps = [np.asarray(c.work[1]) for c in calls]
    if any(m.shape != (slots,) or n.shape != (slots,)
           for m, n in zip(masks, steps)):
        return None
    return [{"kind": "admit" if c.work[0] is not None else "decode",
             "t0": c.t0, "t_ready": c.t_ready, "steps": st, "kv": kv,
             "admitted": int(m.sum()), "decoded": int(n.sum())}
            for c, m, n, (st, kv) in zip(
                calls, masks, steps,
                counts.decode_steps_kv(zip(masks, steps), prompt_len, slots))]


def _intersect(a: Sequence[Tuple[float, float]],
               b: Sequence[Tuple[float, float]]):
    """The intersection of two sorted lists of disjoint closed intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo <= hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def attribute(calls: Sequence[Call], stamps: Sequence[float],
              wall_lo: float, wall_hi: float, wall_end: float):
    """The start of the program's clock on the wall clock, and for each
    of ``stamps`` (the program's done times, seconds after that start)
    the index of the dispatch whose results it follows.

    The start lies in ``[wall_lo, wall_hi]``.  A stamp is taken at a
    host sync: after some call's results were ready, before the next
    call was made (or ``wall_end``).  The start is narrowed to the
    values that place every stamp so; each stamp must then follow one
    dispatch alone, else this raises ``ValueError``.
    """
    ready = [c.wall_ready for c in calls]
    gaps = list(zip(ready, [c.wall0 for c in calls[1:]] + [wall_end]))
    start = [(wall_lo, wall_hi)]
    for d in sorted(set(stamps)):
        start = _intersect(start, [(lo - d, hi - d) for lo, hi in gaps])
    if not start:
        raise ValueError("no start of the program's clock places every "
                         "done stamp after a dispatch")
    lo, hi = start[0][0], start[-1][1]
    index = []
    for d in stamps:
        k = bisect.bisect_right(ready, lo + d) - 1
        if k < 0 or bisect.bisect_right(ready, hi + d) - 1 != k:
            raise ValueError(f"done stamp {d!r} does not follow one dispatch")
        index.append(k)
    return lo, index


def model_config(c: dict):
    """The program's ModelConfig for a published Qwen1.5 config."""
    from repro.configs.base import get_config

    return dataclasses.replace(
        get_config(c["program_arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], param_dtype=c["param_dtype"])


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        p = ctx.params
        self.P, self.G = int(p["prompt_len"]), int(p["max_new"])
        self.slots, self.chunk = int(p["slots"]), int(p["chunk"])
        self.rate = float(p["rate"])
        self.n_requests = max(1, round(self.rate * ctx.seconds))
        self.calls: List[Call] = []

    def _params_tree(self):
        return self.eng.pre.input_sds[0]

    def setup(self):
        from repro.launch.serve import ServeEngine, serve_continuous
        from repro.parallel import make_mesh

        self.serve = serve_continuous
        self.mcfg = model_config(self.ctx.config)
        self.mesh = make_mesh((1, 1), ("data", "model"),
                              devices=self.ctx.devices)
        with phase("engine"):
            self.eng = ServeEngine(self.mcfg, self.mesh, slots=self.slots,
                                   prompt_len=self.P, max_new=self.G,
                                   chunk=self.chunk)
        with phase("weights"):
            self.reseed(self.ctx.seed)
        # warm up both programs as the window calls them: an admission on
        # the fresh state, a pure decode round, and an admission on the
        # state a round returned (laid out otherwise: a program of its own)
        with phase("warm-up"):
            self._serve(self.prompts[: self.slots + 1], rate=0.0,
                        max_new=self.chunk + 2)
        # every dispatch callable of the engine (each counts its calls)
        self.timed = {}
        for name, fn in list(vars(self.eng).items()):
            if callable(fn) and hasattr(fn, "calls"):
                self.timed[name] = _Timed(fn, name, self.calls)
                setattr(self.eng, name, self.timed[name])

    def reseed(self, seed: int):
        """Prompts and weights from ``seed``."""
        from bench import weights

        self.seed = seed
        rng = np.random.default_rng(seed32(seed, 1))
        self.prompts = rng.integers(0, self.ctx.config["vocab_size"],
                                    (self.n_requests, self.P), dtype=np.int32)
        self.params = None    # free the old weights before making new
        self.params = weights.make(self._params_tree(), seed32(seed))

    def _serve(self, prompts, rate, max_new):
        return self.serve(
            self.mcfg, self.mesh, slots=self.slots, prompt_len=self.P,
            max_new=max_new, n_requests=len(prompts), chunk=self.chunk,
            arrival_rate=rate, seed=int(self.ctx.params["arrival_seed"]),
            params=self.params, prompts={"tokens": prompts},
            engine=self.eng)

    def window(self, spans):
        del self.calls[:]
        for t in self.timed.values():
            t.spans = spans
        self.offsets = arrivals(self.n_requests, self.rate,
                                int(self.ctx.params["arrival_seed"]) + 1)
        self.t_start, wall_start = time.perf_counter(), time.time()
        results, _ = self._serve(self.prompts, self.rate, self.G)
        self.t_end, wall_end = time.perf_counter(), time.time()
        self.results = [r for r in results if r is not None]
        # the program's clock starts after the call above, and before
        # the first request was due at the first dispatch
        origin, index = attribute(
            self.calls, [r.t_done for r in self.results], wall_start,
            max(wall_start, self.calls[0].wall0 - self.offsets[0]), wall_end)
        # due times on the host clock, from the program's start
        self.t_origin = self.t_start + (origin - wall_start)
        self.done = np.full(self.n_requests, np.nan)
        for r, k in zip(self.results, index):
            self.done[r.rid] = self.calls[k].t_ready
        self.due = self.t_origin + self.offsets
        self.tokens = {r.rid: np.asarray(r.tokens, np.int32)
                       for r in self.results}

    def finished(self) -> List[int]:
        """Requests served all their tokens."""
        return sorted(i for i, t in self.tokens.items() if len(t) == self.G)

    def sample(self):
        """The finished requests that the reference checks, drawn from
        the seed, as (request id, served tokens)."""
        done = self.finished()
        rng = np.random.default_rng(seed32(self.seed, 2))
        k = min(int(self.ctx.params["check_requests"]), len(done))
        return [(i, self.tokens[i])
                for i in rng.choice(done, size=k, replace=False)]

    def finish(self) -> Outcome:
        from bench import weights
        from bench.refs import qwen as ref

        calls = dispatch_work(self.calls, self.P, self.slots)
        tree = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                            self._params_tree())
        del self.params, self.eng, self.timed, self.calls
        finished = self.finished()
        failed = self.n_requests - len(finished)
        # the program served the schedule this mix defines
        late_schedule = sum(r.t_arrive != self.offsets[r.rid]
                            for r in self.results)

        # the reference over a sample of the finished requests, each
        # prompt followed by the tokens served for it
        w = weights.make(tree, seed32(self.seed))
        checked = self.sample()
        gap = max((ref.served_gap(w, self.ctx.config, self.prompts[i], toks)
                   for i, toks in checked), default=0.0)
        lim = self.ctx.params["limits"]
        checks = [check_le("logit_gap", gap, lim["logit_gap"]),
                  check_le("unfinished", failed, 0),
                  check_le("arrival_mismatch", late_schedule, 0)]

        latency = (self.done - self.due)[finished] * 1e3
        n_tokens = sum(len(t) for t in self.tokens.values())
        window = self.t_end - self.t_start
        return Outcome(
            attempted=self.n_requests, failed=failed,
            end_to_end={"tok_per_s": n_tokens / window},
            counts={"calls": calls, "prompt_len": self.P,
                    "latency_ms": latency.tolist(),
                    "done": self.done[finished].tolist()},
            checks=checks)
