#!/usr/bin/env python3
"""Chip smoke test: drive the ST runtime's main paths once on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips of one host

One chip runs, in order:

* Faces (the paper's 26-neighbour halo solve) at 256³ points per rank on
  a periodic ``(1,1,1)`` grid: ten persistent dataflow iterations with
  ``pack="jnp"`` and with ``pack="pallas"``, each against
  ``faces_oracle`` in NumPy; then a convergence-terminated solve.
* Serving ``qwen1.5-0.5b`` at its published widths (random weights from
  ``--seed``): 8 slots, 512-token prompts, 64 new tokens, resident and
  host-stepped decode on the same params and prompts (tokens must be
  identical), then 16 requests as a t=0 burst through continuous
  batching.

``--chips 4`` runs only what exists across chips: Faces on a periodic
``(2,2,1)`` grid at 256³ per chip with both pack modes against the
oracle, and the ST tensor-parallel MLP block at qwen1.5-0.5b's widths
(one block, then a 4-deep persistent chain) against the stock
``shard_map`` collectives applied as many times.

The script fails (non-zero exit, no result line) at the first fault and
when JAX finds no TPU: there is no CPU fallback.  Per phase it prints
readings taken on the chip (compile and wall seconds, peak device
memory); they are readings, not benchmark metrics.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: bf16 unit roundoff (8-bit significand)
BF16_U = 2.0 ** -8


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"check: {what}: ok", flush=True)


# --------------------------------------------------------------------------
# readings
# --------------------------------------------------------------------------


_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Readings:
    """Sums JAX's own compile-duration events while a phase runs."""

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax

        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        print(f"reading (chip, not a metric) [{name}]: "
              f"compile_s={self.compile_s - c0:.3f} wall_s={wall:.3f} "
              f"persistent_cache_hits={self.cache_hits - h0} "
              f"peak_bytes_in_use={max(peaks)}", flush=True)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def _errors(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    abs_err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    return abs_err, abs_err / scale


def faces_phase(readings: Readings, grid, points, n_iters: int = 10,
                seed: int = 0) -> None:
    """Persistent dataflow Faces with both pack modes vs the oracle."""
    from repro.core.halo import (AXES3, FacesConfig, faces_oracle,
                                 run_faces_persistent)
    from repro.parallel import make_mesh

    cfg = FacesConfig(grid=grid, points=points, dtype="float32",
                      periodic=True, damping=0.03)
    mesh = make_mesh(grid, AXES3)
    u0 = np.random.default_rng(seed).standard_normal(
        (*grid, *points), dtype=np.float32)
    t0 = time.perf_counter()
    ref = u0
    for _ in range(n_iters):
        ref = faces_oracle(ref, cfg)
    print(f"reading (host) [faces oracle x{n_iters}]: "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
    for pack in ("jnp", "pallas"):
        c = dataclasses.replace(cfg, pack=pack)
        name = f"faces grid={grid} points={points} pack={pack}"
        with readings.phase(name):
            mem, stats = run_faces_persistent(c, mesh, u0, n_iters,
                                              mode="dataflow")
            got = np.asarray(mem["u"])
        abs_err, rel_err = _errors(got, ref)
        print(f"reading [{name}]: max_abs_err={abs_err:.3e} "
              f"max_err/max|ref|={rel_err:.3e}", flush=True)
        check(np.isfinite(got).all() and got.shape == ref.shape,
              f"{name}: finite field of shape {ref.shape}")
        # float32 solve vs float32 oracle, op order differing: a few ulp
        # per iteration of a contraction, far inside 1e-5 of the max
        check(rel_err <= 1e-5, f"{name}: matches faces_oracle x{n_iters} "
              f"(max err {rel_err:.2e} of max|ref| <= 1e-5)")
        check(stats.dispatches == 1, f"{name}: one host dispatch")


def converge_phase(readings: Readings, points, tol: float = 1e-6,
                   max_iters: int = 50, seed: int = 1) -> None:
    """Device-terminated Faces solve to a residual tolerance."""
    from repro.core.halo import AXES3, FacesConfig, run_faces_until_converged
    from repro.parallel import make_mesh

    grid = (1, 1, 1)
    cfg = FacesConfig(grid=grid, points=points, dtype="float32",
                      periodic=True, damping=0.03)
    mesh = make_mesh(grid, AXES3)
    u0 = np.random.default_rng(seed).standard_normal(
        (*grid, *points), dtype=np.float32)
    name = f"faces until rms<{tol:g} points={points}"
    with readings.phase(name):
        mem, res, n_done, stats = run_faces_until_converged(
            cfg, mesh, u0, tol=tol, max_iters=max_iters, mode="dataflow")
        u = np.asarray(mem["u"], np.float64)
    rms = float(np.sqrt(np.mean(u * u)))
    print(f"reading [{name}]: n_done={n_done} residuals={res.tolist()} "
          f"host_rms={rms:.6e}", flush=True)
    check(bool(np.all(np.diff(res) < 0)), f"{name}: residual trace decreases")
    check(n_done < max_iters, f"{name}: stopped at {n_done} < {max_iters}")
    check(rms <= tol, f"{name}: host RMS {rms:.3e} <= tol")
    check(stats.dispatches == 1, f"{name}: one host dispatch")


def serve_phase(readings: Readings, cfg, *, slots: int = 8,
                prompt_len: int = 512, gen: int = 64, n_requests: int = 16,
                chunk: int = 16, seed: int = 0) -> None:
    """Resident vs host-stepped decode, then a continuous-batching burst."""
    import jax

    from repro.launch.serve import (ServeEngine, serve, serve_continuous,
                                    synthetic_batch)
    from repro.models import Model
    from repro.parallel import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    with readings.phase(f"{cfg.name} init params (seed {seed})"):
        # op by op, as serve() does without params: one jitted init of
        # all 24 layers' unrolled RNG took 103 s to compile on a v5e
        params, _ = Model(cfg).init(jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
    prompts = synthetic_batch(cfg, np.random.RandomState(seed), n_requests,
                              prompt_len)
    batch_in = {k: v[:slots] for k, v in prompts.items()}

    eng = ServeEngine(cfg, mesh, slots=slots, prompt_len=prompt_len,
                      max_new=gen, chunk=gen - 1)
    gens = {}
    for resident in (True, False):
        name = (f"{cfg.name} serve slots={slots} prompt={prompt_len} "
                f"gen={gen} {'resident' if resident else 'host-stepped'}")
        with readings.phase(name):
            gens[resident], stats = serve(
                cfg, mesh, batch=slots, prompt_len=prompt_len, gen_len=gen,
                seed=seed, device_resident=resident, params=params,
                batch_in=batch_in, engine=eng)
        print(f"reading [{name}]: prefill_s={stats['prefill_s']:.3f} "
              f"decode_s={stats['decode_s']:.3f} "
              f"decode_dispatches={stats['decode_dispatches']}", flush=True)
        check(gens[resident].shape == (slots, gen)
              and bool((gens[resident] >= 0).all()),
              f"{name}: {slots}x{gen} tokens, none padded")
    same = gens[True] == gens[False]
    if not same.all():
        b, t = np.argwhere(~same)[0]
        print(f"reading: resident and host-stepped tokens first differ at "
              f"slot {b} step {t}: {gens[True][b, t]} vs {gens[False][b, t]}; "
              f"{int((~same).sum())} of {same.size} differ", flush=True)
    check(bool(same.all()), "resident decode tokens == host-stepped tokens")

    name = (f"{cfg.name} continuous slots={slots} requests={n_requests} "
            f"burst chunk={chunk}")
    with readings.phase(name):
        results, cstats = serve_continuous(
            cfg, mesh, slots=slots, prompt_len=prompt_len, max_new=gen,
            n_requests=n_requests, chunk=chunk, arrival_rate=0.0, seed=seed,
            params=params, prompts=prompts)
    print(f"reading [{name}]: total_s={cstats['total_s']:.3f} "
          f"dispatches={cstats['dispatches']} "
          f"admit_dispatches={cstats['admit_dispatches']} "
          f"decode_dispatches={cstats['decode_dispatches']}", flush=True)
    check(len(results) == n_requests
          and all(r is not None and len(r.tokens) == gen for r in results),
          f"{name}: all {n_requests} requests finished with {gen} tokens")
    check(cstats["prefill_dispatches"] == 0,
          f"{name}: prefill_dispatches == 0 (admission is composed)")
    agree = sum(np.array_equal(results[r].tokens, gens[True][r])
                for r in range(slots))
    print(f"reading [{name}]: {agree} of {slots} requests emit the same "
          f"tokens as the single-shot batch", flush=True)


def tp_phase(readings: Readings, n_chips: int, *, m: int = 8192,
             k: int = 1024, f: int = 2816, layers: int = 4,
             seed: int = 0) -> None:
    """The ST TP MLP block (one block, then a persistent chain) against
    the stock shard_map collectives."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import PersistentEngine, collectives
    from repro.core.engine_fused import FusedEngine
    from repro.parallel import make_mesh

    mesh = make_mesh((n_chips,), ("x",))
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16
    x = rng.standard_normal((m, k), dtype=np.float32).astype(bf16)
    w1 = (rng.standard_normal((k, f), dtype=np.float32)
          / np.sqrt(k)).astype(bf16)
    w2 = (rng.standard_normal((f, k), dtype=np.float32)
          / np.sqrt(f)).astype(bf16)
    specs = {"x": P("x"), "w1": P(None, "x"), "w2": P("x")}
    host = {"x": x, "w1": w1, "w2": w2}

    def placed():
        return {n: jax.device_put(v, NamedSharding(mesh, specs[n]))
                for n, v in host.items()}

    for depth in (1, layers):
        tp = collectives.build_tp_block(mesh, "x", m, k, f, dtype=bf16,
                                        chain=depth > 1)
        name = (f"tp block m={m} k={k} f={f} bf16 x{n_chips} chips "
                f"depth={depth}")
        with readings.phase(name):
            if depth == 1:
                eng = FusedEngine(tp.program, mode="dataflow", donate=True)
            else:
                eng = PersistentEngine(tp.program.persistent(depth),
                                       mode="dataflow", donate=True)
            got = np.asarray(eng(eng.init_buffers(host))[tp.output],
                             np.float32)
        with readings.phase(name + " stock reference"):
            a = placed()
            want = a["x"]
            for _ in range(depth):
                want = tp.reference_stock(want, a["w1"], a["w2"])
            want = np.asarray(want, np.float32)
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        abs_err, max_rel = _errors(got, want)
        print(f"reading [{name}]: fro_rel_err={rel:.3e} "
              f"max_abs_err={abs_err:.3e} max_err/max|ref|={max_rel:.3e}",
              flush=True)
        check(np.isfinite(got).all() and got.shape == (m, k),
              f"{name}: finite output of shape {(m, k)}")
        # the ring reduce-scatter rounds to bf16 after each of its n-1
        # adds where the stock reduce-scatter may not: allow 4 bf16
        # roundoffs of normwise error per layer
        bound = 4 * depth * BF16_U
        check(rel <= bound, f"{name}: matches reference_stock x{depth} "
              f"(normwise {rel:.2e} <= {bound:.2e})")


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def _device_or_exit(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
                 f"this check has no CPU fallback")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} TPU "
                 f"devices, JAX found {len(devices)}")
    return devices


def _import_repro_or_exit() -> None:
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no repro package under {src}; run it from a "
                 f"checkout of the repository")
    sys.path.insert(0, src)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: Faces 256³ + qwen1.5-0.5b serving; "
                         "4: (2,2,1) Faces + the ST TP block")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and fields")
    args = ap.parse_args(argv)

    devices = _device_or_exit(args.chips)
    _import_repro_or_exit()
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}", flush=True)
    readings = Readings()
    points = (256, 256, 256)
    if args.chips == 1:
        from repro.configs.base import get_config

        faces_phase(readings, (1, 1, 1), points, seed=args.seed)
        converge_phase(readings, points, seed=args.seed + 1)
        serve_phase(readings, get_config("qwen1.5-0.5b"), seed=args.seed)
    else:
        faces_phase(readings, (2, 2, 1), points, seed=args.seed)
        tp_phase(readings, args.chips, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
