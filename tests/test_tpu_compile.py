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
import re

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


@pytest.fixture(scope="module")
def f20k_grid():
    """cfg III at 20,000 files per site, 2 lanes, 0.25 days at 60 s."""
    specs = expand_grid({"base": "III", "n_files": 20_000, "days": 0.25,
                         "seed": [0, 1]})
    return pack_specs(specs, tick=60.0)


def _f20k_hlo(grid, device):
    """The jnp grid program for ``grid``, compiled for one ``device``."""
    one = SingleDeviceSharding(device)
    program = batched._grid_program.__wrapped__(
        len(grid.site_names), grid.max_jobs_per_tick, grid.n_months, "jnp")
    return program.lower(*_arg_shapes(grid, one, one)).compile().as_text()


@pytest.mark.parametrize("target", ["cpu", "v5e"])
def test_grid_program_hlo_names_every_tick_phase(target, request, f20k_grid,
                                                 no_compile_cache):
    """Each phase's ``jax.named_scope`` reaches the compiled program's
    ``op_name`` metadata, where a profiler trace can be mapped to it."""
    import jax

    device = (request.getfixturevalue("topo").devices[0] if target == "v5e"
              else jax.devices("cpu")[0])
    hlo = _f20k_hlo(f20k_grid, device)
    found = set(re.findall(r'op_name="[^"]*?/(tick\.\w+)[/"]', hlo))
    # ``tick.series`` exists only under ``record_series``
    assert found == {scope for phase, scope in batched.TICK_SCOPES.items()
                     if phase != "series"}


@pytest.mark.parametrize("target", ["cpu", "v5e"])
def test_grid_program_selects_queue_heads_without_a_sort(
        target, request, f20k_grid, no_compile_cache):
    """The waiting queue's W heads are W reductions over the ticket
    plane: the compiled program holds no sort and no top-k, and the
    ``tick.waitq`` phase still holds ops."""
    import jax

    device = (request.getfixturevalue("topo").devices[0] if target == "v5e"
              else jax.devices("cpu")[0])
    hlo = _f20k_hlo(f20k_grid, device)
    assert " sort(" not in hlo
    assert not re.search(r"(?i)top_?k", hlo)
    assert re.search(r'op_name="[^"]*?/tick\.waitq[/"]', hlo)


@pytest.mark.parametrize("target", ["cpu", "v5e"])
def test_grid_program_applies_plans_to_unflattened_planes(
        target, request, f20k_grid, no_compile_cache):
    """``tick.apply`` scatters into the ``[S, F]`` planes as the scan
    carries them: no instruction of the phase has a flattened plane's
    shape, ``[L, S·F]`` or ``[L·S·F]`` (on the TPU each such reshape is
    a relayout copy of the whole plane), and the phase still holds its
    scatters."""
    import jax

    device = (request.getfixturevalue("topo").devices[0] if target == "v5e"
              else jax.devices("cpu")[0])
    hlo = _f20k_hlo(f20k_grid, device)
    L, S, F = f20k_grid.sizes.shape
    flat = {f"{L},{S * F}", f"{L * S * F}"}
    apply_ops = [line for line in hlo.splitlines()
                 if re.search(r'op_name="[^"]*?/tick\.apply[/"]', line)]
    shapes = {m for line in apply_ops
              for m in re.findall(r"= \w+\[([\d,]+)\]", line)}
    assert not shapes & flat, [line.split(", metadata")[0]
                               for line in apply_ops
                               if any(f"[{f}]" in line for f in flat)]
    scatters = [line for line in apply_ops if re.search(
        r"= \S+ (scatter|fusion)\(.*op_name=\"[^\"]*?/tick\.apply/"
        r"scatter-", line)]
    assert scatters


def test_v5e_grid_program_keeps_one_plane_wide_cumsum(topo, f20k_grid,
                                                      no_compile_cache):
    """On the TPU a cumsum lowers to ``reduce-window``s. The migrations'
    ranks are a blocked count on the MXU, so of the cfg III program's
    plane-wide ones (at least lanes x S x F elements) only the GCS gate's
    is left: it runs over the site-major flattened ``[L, S·F]`` vector,
    which no ``[L, S, ...]`` shape of a per-site count has."""
    hlo = _f20k_hlo(f20k_grid, topo.devices[0])
    L, S, F = f20k_grid.sizes.shape
    shapes = [[int(d) for d in dims.split(",")] for dims in re.findall(
        r"= \w+\[([\d,]+)\]\{[^}]*\} reduce-window\(", hlo)]
    wide = [dims for dims in shapes if np.prod(dims) >= L * S * F]
    assert len(wide) == 1 and wide[0][:2] != [L, S], wide
    assert re.search(r'op_name="[^"]*?/tick\.migrate/dot_general"', hlo)


def test_shard_program_compiles_over_four_v5e_without_collectives(
        topo, paper_grid, monkeypatch, no_compile_cache):
    from repro.parallel import sharding

    grid = paper_grid
    mesh = Mesh(np.array(topo.devices[:4]), (sharding.LANES_AXIS,))
    # _shard_program builds its mesh from the local devices; hand it the
    # described chips instead.
    monkeypatch.setattr(sharding, "lane_mesh", lambda n: mesh)
    program = batched._shard_program.__wrapped__(
        len(grid.site_names), grid.max_jobs_per_tick, grid.n_months, None, 4)
    args = _arg_shapes(grid, NamedSharding(mesh, PartitionSpec()),
                       NamedSharding(mesh, PartitionSpec(sharding.LANES_AXIS)))
    hlo = program.lower(*args).compile().as_text()
    found = [c for c in COLLECTIVES if c in hlo]
    assert not found, f"lane-sharded program contains collectives: {found}"
