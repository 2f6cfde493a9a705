"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed here, so the main path's kernels and the
Faces programs are compiled at their real sizes for a described
``v5e:2x2`` host: what the chip's compiler refuses (VMEM overflow, a
layout Mosaic cannot lower, a program that does not fit HBM) fails here
at no chip time.  Nothing runs, so these say nothing about results or
times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers all import this file.  The kernels need no steering into
compiled mode: ``repro.kernels.ops`` lowers the interpreter only for a
CPU target, and every test asserts that the compiled program holds the
Mosaic kernel (``tpu_custom_call``).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

N = 256  # Faces points per rank and axis: 64 MiB of float32 per field


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _region(direction):
    from repro.core.halo import _region_for
    return _region_for(direction, (N, N, N))


REGIONS = {"face": (0, 0, 1), "edge": (0, 1, -1), "corner": (1, -1, 1)}


@pytest.mark.parametrize("kind", sorted(REGIONS))
def test_halo_pack_compiles_at_256(one_chip, kind):
    from repro.kernels import ops
    region = _region(REGIONS[kind])
    u = jax.ShapeDtypeStruct((N, N, N), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda u: ops.halo_pack(u, region), u)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kind", sorted(REGIONS))
def test_halo_unpack_add_compiles_at_256(one_chip, kind):
    from repro.kernels import ops
    region = _region(REGIONS[kind])
    shape = tuple(s.stop - s.start for s in region)
    u = jax.ShapeDtypeStruct((N, N, N), jnp.float32, sharding=one_chip)
    msg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda u, m: ops.halo_unpack_add(u, m, region),
                       donate_argnums=0).lower(u, msg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # in place: the block is aliased, never copied into a temporary
    assert compiled.memory_analysis().temp_size_in_bytes < N * N * N * 4


# one Faces direct26 group at 256³: a face, an edge and a corner slab
SEGMENTS = [(1, N, N), (1, N, 1), (1, 1, 1)]


def test_pack_segments_compiles(one_chip):
    from repro.kernels.halo_pack import pack_segments_call
    slabs = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
             for s in SEGMENTS]
    text = _compiled_text(lambda *a: pack_segments_call(a), *slabs)
    assert "tpu_custom_call" in text


def test_unpack_segments_compiles(one_chip):
    from repro.kernels.halo_pack import unpack_segments_call
    total = sum(int(np.prod(s)) for s in SEGMENTS)
    buf = jax.ShapeDtypeStruct((total,), jnp.float32, sharding=one_chip)
    text = _compiled_text(lambda b: unpack_segments_call(b, SEGMENTS), buf)
    assert "tpu_custom_call" in text


def _faces_compiled(devices, grid, pack, residual=False):
    from repro.core.engine_persistent import PersistentEngine
    from repro.core.halo import (AXES3, FacesConfig, build_faces_program,
                                 global_residual_fn)
    from repro.parallel import make_mesh

    mesh = make_mesh(grid, AXES3, devices=devices)
    cfg = FacesConfig(grid=grid, points=(N, N, N), dtype="float32",
                      periodic=True, damping=0.03, pack=pack)
    prog = build_faces_program(cfg, mesh).persistent(10)
    eng = PersistentEngine(prog, mode="dataflow", donate=True,
                           reduce_fn=global_residual_fn(cfg) if residual
                           else None)
    return eng.lower().compile()


def test_faces_pallas_persistent_compiles_at_256_on_one_chip(topo):
    compiled = _faces_compiled(topo.devices[:1], (1, 1, 1), "pallas")
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("pack", ["jnp", "pallas"])
def test_faces_2x2x1_compiles_on_2x2(topo, pack):
    compiled = _faces_compiled(topo.devices, (2, 2, 1), pack)
    text = compiled.as_text()
    # the halo crosses chips: the exchange is real collective traffic
    assert "collective-permute" in text
    assert ("tpu_custom_call" in text) == (pack == "pallas")


def test_faces_scopes_survive_the_tpu_compiler(topo):
    """The benchmark's Faces program, compiled for one chip: its fusions
    keep the queue ops' scopes in their ``op_name``, by which a trace's
    device time is named per stage."""
    import re

    text = _faces_compiled(topo.devices[:1], (1, 1, 1), "jnp",
                           residual=True).as_text()
    fusions = re.findall(r' fusion\(.*op_name="([^"]*)"', text)
    scopes = {p for name in fusions for p in name.split("/")[:-1]}
    assert {"interior", "exchange", "residual", "unpack0"} <= scopes
