"""Public jit'd wrappers for the Pallas kernels.

Every op lowers per target platform: where the program is compiled for
the CPU the kernel runs in the Pallas interpreter (how it is validated
against :mod:`.ref`), everywhere else it is the compiled Mosaic kernel.
The choice follows the devices the call is compiled for
(``lax.platform_dependent`` lowers only the branch of that platform),
not the process's default backend, so there is no switch that runs a
kernel interpreted on the chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .faces_stencil import faces_stencil7_call
from .flash_attention import flash_attention_call
from .halo_pack import halo_pack_call, halo_unpack_add_call
from .rmsnorm import rmsnorm_call
from .ssd_scan import ssd_scan_call


def _on_target(kernel, *args, **static):
    """``kernel(*args, **static, interpret=...)`` with ``interpret`` set
    by the platform the enclosing program is lowered for."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, **static, interpret=True),
        default=functools.partial(kernel, **static, interpret=False))


def _hashable_region(region):
    return tuple((s.start or 0, s.stop) for s in region)


def _region_from_hashable(hr):
    return tuple(slice(a, b) for a, b in hr)


# -- halo pack family ---------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1,))
def _halo_pack(u, hregion):
    return _on_target(halo_pack_call, u, region=_region_from_hashable(hregion))


def halo_pack(u: jax.Array, region: Tuple[slice, ...]) -> jax.Array:
    return _halo_pack(u, _hashable_region(region))


@functools.partial(jax.jit, static_argnums=(2,))
def _halo_unpack_add(u, msg, hregion):
    return _on_target(halo_unpack_add_call, u, msg,
                      region=_region_from_hashable(hregion))


def halo_unpack_add(u: jax.Array, msg: jax.Array,
                    region: Tuple[slice, ...]) -> jax.Array:
    return _halo_unpack_add(u, msg, _hashable_region(region))


# -- Faces interior stencil ----------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3,))
def _faces_stencil7(core, lo, hi, block_planes):
    return _on_target(faces_stencil7_call, core, lo, hi,
                      block_planes=block_planes)


def faces_stencil7(core: jax.Array, lo: jax.Array, hi: jax.Array, *,
                   block_planes: Optional[int] = None) -> jax.Array:
    """7-point periodic smoothing of a (px, py, pz) block with x-ghost
    planes ``lo``/``hi`` (see :mod:`.faces_stencil`)."""
    return _faces_stencil7(core, lo, hi, block_planes)


# -- rmsnorm -------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _rmsnorm2d(x, w, eps, weight_offset, block_rows):
    return _on_target(rmsnorm_call, x, w, eps=eps, weight_offset=weight_offset,
                      block_rows=block_rows)


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6,
            weight_offset: float = 0.0, block_rows: int = 128) -> jax.Array:
    """Fused RMSNorm over the last dim; any leading dims."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = _rmsnorm2d(x2, w, eps, weight_offset, block_rows)
    return y.reshape(*lead, x.shape[-1])


# -- flash attention -----------------------------------------------------------


@functools.partial(jax.jit, static_argnums=tuple(range(3, 10)))
def _flash(q, k, v, causal, scale, window, logit_softcap, q_offset,
           block_q, block_k):
    return _on_target(
        flash_attention_call, q, k, v, causal=causal, scale=scale,
        window=window, logit_softcap=logit_softcap, q_offset=q_offset,
        block_q=block_q, block_k=block_k)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    logit_softcap: Optional[float] = None,
                    q_offset: int = 0, block_q: int = 128,
                    block_k: int = 128) -> jax.Array:
    return _flash(q, k, v, causal, scale, window, logit_softcap, q_offset,
                  block_q, block_k)


# -- SSD scan -------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(6, 7))
def _ssd(x, dt, A, Bm, C, init_state, chunk, return_state):
    return _on_target(
        lambda x, dt, A, Bm, C, s, *, interpret: ssd_scan_call(
            x, dt, A, Bm, C, init_state=s, chunk=chunk,
            return_state=return_state, interpret=interpret),
        x, dt, A, Bm, C, init_state)


def ssd_scan(x, dt, A, Bm, C, *, init_state=None, chunk: int = 128,
             return_state: bool = False):
    if init_state is None:
        B, _, H, P = x.shape
        N = Bm.shape[-1]
        init_state = jnp.zeros((B, H, P, N), jnp.float32)
    return _ssd(x, dt, A, Bm, C, init_state, chunk, return_state)


__all__ = [
    "halo_pack", "halo_unpack_add", "faces_stencil7", "rmsnorm",
    "flash_attention", "ssd_scan", "ref",
]
