"""The batched sweep compiles for a TPU v5e at the paper's catalogue width.

These compiles need no chip: the TPU compiler is installed and compiles
for a described v5e:2x2 topology. The topology is described inside a
fixture, never while a module is imported, and everything built from it
(shardings, the mesh) is built in the tests. A compile that passes is
not a chip run; it says nothing about results or times.

Width: Table 5 cfg III, S=2 sites, F=10^6 files per site, 8 dynamics
lanes, 0.25 days at the paper's 10 s clock (2161 ticks); the K/J job
windows are ``pack_specs``'s buckets for that horizon.
"""

import os

import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec,
    SingleDeviceSharding,
)

from repro.core.scenarios import expand_grid, pack_specs
from repro.sim import batched

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16e9

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def paper_grid():
    specs = expand_grid({"base": "III", "n_files": 1_000_000, "days": 0.25,
                         "seed": [0, 1, 2, 3], "cache_tb": [50.0, 100.0]})
    grid = pack_specs(specs, tick=10.0)
    assert grid.n_lanes == 8 and grid.n_ticks == 2161
    assert grid.sizes.shape[1:] == (2, 1_000_000)
    return grid


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to JAX's persistent
    cache but cannot be read back without the chip; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _arg_shapes(grid, shared_sharding, lane_sharding):
    """Shapes (no arrays) of the grid program's 5 shared + 15 lane args."""
    import jax

    T = grid.n_ticks
    shared = (np.asarray(grid.times), np.asarray(grid.dts),
              np.asarray(grid.month_idx), np.arange(T, dtype=np.int32),
              np.float32(grid.horizon))
    lanes = [np.asarray(getattr(grid, n)) for n in batched._LANE_FIELDS]
    return ([jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=shared_sharding)
             for a in shared]
            + [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=lane_sharding)
               for a in lanes])


def test_jnp_grid_program_compiles_for_one_v5e(topo, paper_grid,
                                               no_compile_cache):
    grid = paper_grid
    one_chip = SingleDeviceSharding(topo.devices[0])
    program = batched._grid_program.__wrapped__(
        len(grid.site_names), grid.max_jobs_per_tick, grid.n_months, "jnp")
    compiled = program.lower(*_arg_shapes(grid, one_chip, one_chip)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, mem


def test_shard_program_compiles_over_four_v5e_without_collectives(
        topo, paper_grid, monkeypatch, no_compile_cache):
    from repro.parallel import sharding

    grid = paper_grid
    mesh = Mesh(np.array(topo.devices[:4]), (sharding.LANES_AXIS,))
    # _shard_program builds its mesh from the local devices; hand it the
    # described chips instead.
    monkeypatch.setattr(sharding, "lane_mesh", lambda n: mesh)
    program = batched._shard_program.__wrapped__(
        len(grid.site_names), grid.max_jobs_per_tick, grid.n_months, "jnp",
        None, 4)
    args = _arg_shapes(grid, NamedSharding(mesh, PartitionSpec()),
                       NamedSharding(mesh, PartitionSpec(sharding.LANES_AXIS)))
    hlo = program.lower(*args).compile().as_text()
    found = [c for c in COLLECTIVES if c in hlo]
    assert not found, f"lane-sharded program contains collectives: {found}"
