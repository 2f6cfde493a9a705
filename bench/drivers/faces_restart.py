"""Faces traffic: persistent dispatches back to back, each from the seeded
field.

A solver that watches convergence: every dispatch runs
``iters_per_dispatch`` device-resident Faces iterations
(``PersistentEngine`` over ``build_faces_program``, with the global RMS
residual as its per-iteration reduction), and the host reads the
residual trace after each dispatch.  Each dispatch starts again from the
field made from the seed, copied on the device, because a damped field
decays to zero within about a hundred iterations and an undamped one
overflows.

Parameters (``bench/traffic/<mix>.json``): ``grid`` (ranks per axis,
one rank per chip), ``iters_per_dispatch``, ``warmup_dispatches`` and
``limits`` (of the numbers compared with the reference).
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import counts
from bench.harness import Outcome, check_le, phase, seed32


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        c, p = ctx.config, ctx.params
        self.grid = tuple(p["grid"])
        self.points = tuple(c["points_per_rank"])
        self.iters = int(p["iters_per_dispatch"])
        self.damping = float(c["damping"])
        self.limits = p["limits"]
        if int(np.prod(self.grid)) != ctx.chips:
            raise ValueError(f"grid {self.grid} needs {np.prod(self.grid)} "
                             f"chips, the cell has {ctx.chips}")

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core.engine_persistent import PersistentEngine
        from repro.core.halo import (AXES3, FacesConfig, build_faces_program,
                                     global_residual_fn)
        from repro.parallel import make_mesh

        c = self.ctx.config
        cfg = FacesConfig(grid=self.grid, points=self.points,
                          dtype=c["dtype"], granularity=c["granularity"],
                          batched=c["batched"], pack=c["pack"],
                          periodic=c["periodic"], damping=self.damping)
        mesh = make_mesh(self.grid, AXES3, devices=self.ctx.devices)
        with phase("program"):
            prog = build_faces_program(cfg, mesh).persistent(self.iters)
            self.eng = PersistentEngine(prog, mode=c["mode"], donate=True,
                                        reduce_fn=global_residual_fn(cfg))
        sharding = self.eng.shardings()["u"]
        shape = (*self.grid, *self.points)
        dtype = jnp.dtype(c["dtype"])
        self._field = jax.jit(lambda k: jax.random.normal(k, shape, dtype),
                              out_shardings=sharding)
        self.refresh = jax.jit(jnp.copy, out_shardings=sharding)
        self.reseed(self.ctx.seed)
        self.mem = self.eng.init_buffers()
        with phase("warm-up"):
            for _ in range(int(self.ctx.params["warmup_dispatches"])):
                self._dispatch(None)

    def reseed(self, seed: int):
        """The initial field from ``seed``, made on the device."""
        import jax

        self.u0 = self._field(jax.random.PRNGKey(seed32(seed)))

    def reference(self, dtype=None):
        """The reference's field and residual trace from the seeded field,
        on one chip, in ``dtype`` (float32 unless given)."""
        import jax
        import jax.numpy as jnp

        from bench.refs import faces as ref

        u0 = jax.device_put(self.u0, self.ctx.devices[0])
        u, r = ref.run(u0, n_iters=self.iters, damping=self.damping,
                       dtype=dtype or jnp.float32)
        return np.asarray(u, np.float64), np.asarray(r, np.float64)

    @staticmethod
    def errors(field, res, want_u, want_r):
        """The field's largest error relative to the reference's largest
        value, and per dispatch the residuals' largest relative error."""
        field_err = np.max(np.abs(field - want_u)) / np.max(np.abs(want_u))
        res = np.reshape(res, (-1, len(want_r)))
        return field_err, np.max(np.abs(res - want_r) / want_r, axis=1)

    def _dispatch(self, spans):
        if spans is not None:
            spans.boundary()
        span = spans or (lambda name: contextlib.nullcontext())
        with span("bench.refresh"):
            self.mem["u"] = self.refresh(self.u0)
        with span("bench.enqueue"):
            self.mem, red = self.eng(self.mem)
        with span("bench.read_residuals"):
            return np.asarray(red)

    def window(self, spans):
        self.residuals = []
        t0 = time.perf_counter()
        while True:
            self.residuals.append(self._dispatch(spans))
            if time.perf_counter() - t0 >= self.ctx.seconds:
                break
        self.elapsed = time.perf_counter() - t0

    def finish(self) -> Outcome:
        field = np.asarray(self.mem["u"], np.float64)
        del self.mem, self.eng
        want_u, want_r = self.reference()
        field_err, per_dispatch = self.errors(field, self.residuals,
                                              want_u, want_r)
        lim = self.limits
        checks = [
            check_le("field_rel_err", field_err, lim["field_rel_err"]),
            check_le("resid_rel_err", np.max(per_dispatch),
                     lim["resid_rel_err"]),
        ]
        n = len(per_dispatch)
        n_iters = n * self.iters
        return Outcome(
            attempted=n,
            failed=int(np.sum(~(per_dispatch <= lim["resid_rel_err"]))),
            end_to_end={"iter_ms": self.elapsed * 1e3 / n_iters},
            counts={"iters_per_dispatch": self.iters,
                    # every measured iteration ran on a live field: the
                    # reference's last residual, far above underflow
                    "ref_last_residual": float(want_r[-1]),
                    "iter_min_bytes": counts.faces_iter_min_bytes(
                        self.points, np.dtype(self.ctx.config["dtype"]).itemsize)},
            checks=checks)
