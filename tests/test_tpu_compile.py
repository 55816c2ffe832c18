"""Compile the main-path programs for a described TPU v5e (no chip needed).

The TPU compiler is installed with jax: it compiles for a topology that is
described, not attached, and refuses what the chip would refuse — VMEM
overflows, unsupported Pallas primitives, programs that do not fit HBM.
Interpret-mode tests cannot see any of that.  Sizes are the paper's 1000K
test group: m = 1,000,000 points, 4,096-query batches.

The topology is described inside a module fixture only: loading libtpu at
import time would make pytest-xdist workers collect different tests.
Programs are compiled in this process (never a child: the worker that
loaded libtpu holds it), with the persistent compilation cache off — a
compile for a described chip cannot be read back on the CPU.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as PS,
                          SingleDeviceSharding)

from repro.core import grid as G
from repro.core import pipeline as P
from repro.core.pipeline import AidwConfig
from repro.data.pipeline import spatial_points
from repro.kernels.aidw import ops as aidw_ops

M, N, K = 1_000_000, 4096, 15


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec():
    """The grid the session plans for 1000K uniform points (host-only)."""
    return G.plan_grid(spatial_points(M, seed=0)[:, :2])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _plan_args(spec, sharding):
    """Shapes of a capacity-padded 1000K plan (table, points, values)."""
    f32 = lambda n: _shape((n,), jnp.float32, sharding)  # noqa: E731
    table = G.CellTable(
        sx=f32(M), sy=f32(M), sz=f32(M),
        cell_start=_shape((spec.n_cells + 1,), jnp.int32, sharding),
        order=_shape((M,), jnp.int32, sharding))
    return table, _shape((M, 2), jnp.float32, sharding), f32(M)


def _check(compiled, kernel: bool) -> None:
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem


@pytest.mark.parametrize("cfg,kernel", [
    (AidwConfig(), False),
    (AidwConfig(stage2="local"), False),
    (AidwConfig(stage2="tiled", interpret=False), True),
], ids=["naive", "local", "tiled"])
def test_session_execute_compiles_for_v5e(one_chip, spec, cfg, kernel):
    assert M % P.PLAN_PAD_MULTIPLE == 0
    table, pts, vals = _plan_args(spec, one_chip)
    compiled = P._session_execute_donate.lower(
        spec, cfg, P._study_area(spec), table, pts, vals,
        _shape((N, 2), jnp.float32, one_chip),
        _shape((), jnp.int32, one_chip)).compile()
    _check(compiled, kernel)


def test_fused_stage2_kernel_compiles_for_v5e(one_chip):
    f32 = lambda *s: _shape(s, jnp.float32, one_chip)  # noqa: E731
    compiled = aidw_ops.fused_stage2.lower(
        f32(N, 2), f32(M, 2), f32(M), f32(N), n_points=f32(), area=f32(),
        interpret=False).compile()
    _check(compiled, True)


def test_local_kernel_compiles_for_v5e(one_chip):
    """The local kernel gathers nothing in-kernel and holds no m-long row
    in VMEM (both refused by the chip's compiler before)."""
    f32 = lambda *s: _shape(s, jnp.float32, one_chip)  # noqa: E731
    compiled = aidw_ops.local_interpolate.lower(
        f32(N, K), _shape((N, K), jnp.int32, one_chip), f32(M), f32(N),
        interpret=False).compile()
    _check(compiled, True)


def test_sharded_session_execute_compiles_for_4_chips(topo, spec):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("q",))
    rep = NamedSharding(mesh, PS())
    table, pts, vals = _plan_args(spec, rep)
    fn = P.sharded_session_execute(mesh, donate=True)
    compiled = fn.lower(
        spec, AidwConfig(), P._study_area(spec), table, pts, vals,
        _shape((N, 2), jnp.float32, NamedSharding(mesh, PS("q", None))),
        _shape((), jnp.int32, rep)).compile()
    _check(compiled, False)
