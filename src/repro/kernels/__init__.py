"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
a jit'd wrapper in ops.py, and a pure-jnp oracle in ref.py.  The
wrappers compile the kernel for the TPU and interpret it where the
program is lowered for the CPU.
"""
from . import ops, ref
from .ops import (
    faces_stencil7,
    flash_attention,
    halo_pack,
    halo_unpack_add,
    rmsnorm,
    ssd_scan,
)

__all__ = [
    "ops", "ref", "faces_stencil7", "flash_attention", "halo_pack",
    "halo_unpack_add", "rmsnorm", "ssd_scan",
]
