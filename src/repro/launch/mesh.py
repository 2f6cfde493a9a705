"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state.  Target: TPU v5e, 256 chips/pod (16×16 2-D
torus), optional 2-pod deployment (512 chips).

Axes:
* ``data``  — batch / FSDP sharding (16-way per pod);
* ``model`` — tensor/expert parallel (16-way, matches the torus row);
* ``pod``   — (multi-pod) data-parallel replication across pods; the
  gradient all-reduce over this axis crosses the inter-pod DCI and is
  what the multi-pod dry-run proves out.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over host (CPU) devices for tests/examples."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# Hardware constants (TPU v5e) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (~4 usable links/chip)
ICI_LINKS = 4
DCI_BW = 25e9                  # inter-pod (conservative)
