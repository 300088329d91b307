"""The tick ranks its migrations with one blocked prefix count.

``repro.sim.batched._prefix_count`` counts a 0/1 mask along the file
axis in blocks, on the MXU, and ``_migration_plan`` derives the queued
ordinals from that one count. The counts are integers below 2^24, so
both must equal the two plane-wide cumsums they replace bit for bit:
the count itself, the plan of one tick, and every output of the whole
program.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scenarios import ScenarioSpec, pack_specs
from repro.sim import batched
from repro.sim.batched import (
    _count_block,
    _migration_plan,
    _prefix_count,
    simulate_packed,
)
from repro.sim.sweep import run_sweep


def _two_cumsum_plan(mig, q_empty, free_m):
    """The migration plan as the tick formed it before ``_prefix_count``:
    a cumsum for the rank among migrations and another for the rank
    among the queued ones."""
    rank = jnp.cumsum(mig.astype(jnp.float32), axis=1) - 1.0
    direct = mig & q_empty & (rank < free_m)
    queued = mig & ~direct
    qrank = jnp.cumsum(queued.astype(jnp.int32), axis=1) - 1
    return direct, queued, qrank


# ------------------------------------------------------------ the count
@pytest.mark.parametrize("density", [0.0, 1e-3, 0.5, 1.0])
@pytest.mark.parametrize("n", [1, 127, 128, 20_000, 1_000_000, 1_000_003])
def test_prefix_count_equals_cumsum(n, density):
    """Blocks that divide the axis (20,000 and 10^6) and padded ones (the
    rest, 1,000,003 being prime) give the cumsum's bits."""
    mask = np.random.default_rng(n).random((2, n)) < density
    got = jax.jit(_prefix_count)(mask)
    want = jnp.cumsum(jnp.asarray(mask).astype(jnp.float32), axis=-1)
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))


def test_count_block_divides_the_axis_where_it_can():
    assert _count_block(1_000_000) == 250
    assert _count_block(20_000) == 250
    assert _count_block(4096) == 256
    assert _count_block(1_000_003) == 128
    assert _count_block(1) == 128


# ------------------------------------------------------ one tick's plan
F_PLAN = 20_000


def _plan_inputs(queue, free, rng):
    """Two sites' migrations (about 400 each, and a burst of 300 at the
    head of site 1) with the link queue empty, busy, or empty on site 0
    alone, and ``free`` slots on each link."""
    mig = rng.random((2, F_PLAN)) < 0.02
    mig[1, :300] = True
    q_empty = {"empty": [True, True], "busy": [False, False],
               "mixed": [True, False]}[queue]
    q_empty = np.array(q_empty)[:, None]
    free_m = np.full((2, 1), float(free), np.float32)
    return mig, q_empty, free_m


@pytest.mark.parametrize("free", [0, 3, 100])
@pytest.mark.parametrize("queue", ["empty", "busy", "mixed"])
def test_migration_plan_equals_two_cumsums(queue, free):
    """Direct starts, the queued files' tickets and the link's next
    ticket are those of the two-cumsum plan."""
    mig, q_empty, free_m = _plan_inputs(queue, free,
                                        np.random.default_rng(free))
    lq_next = np.array([7, 1_000_000], np.int32)
    old_ticket = np.full((2, F_PLAN), -5, np.int32)

    def plan_outputs(plan):
        direct, queued, qrank = jax.jit(plan)(mig, q_empty, free_m)
        tickets = jnp.where(queued, lq_next[:, None] + qrank, old_ticket)
        return (np.asarray(direct), np.asarray(queued), np.asarray(tickets),
                lq_next + np.asarray(queued).sum(axis=1))

    got = plan_outputs(_migration_plan)
    want = plan_outputs(_two_cumsum_plan)
    for name, g, w in zip(("direct", "queued", "tickets", "lq_next"),
                          got, want):
        np.testing.assert_array_equal(g, w, err_msg=name)
    direct, queued = got[0], got[1]
    assert queued.any() == (queue != "empty" or free < 300)
    assert direct.sum() == sum(min(free, int(m.sum()))
                               for m, e in zip(mig, q_empty[:, 0]) if e)


# ------------------------------------------------------- whole program
def _with_plan(plan, run):
    """``run()`` with the tick's migration plan formed by ``plan``."""
    saved = batched._migration_plan
    batched._grid_program.cache_clear()
    batched._migration_plan = plan
    try:
        return run()
    finally:
        batched._migration_plan = saved
        batched._grid_program.cache_clear()


#: The benchmark's three deployments at 20,000 files per site: cfg III,
#: cfg II and cfg III under a 20 TB bucket, 0.25 days at 60 s.
DEPLOYMENTS = {
    "cfgIII": dict(base="III"),
    "cfgII": dict(base="II"),
    "cfgIII-gcs20": dict(base="III", gcs_limit_tb=20.0),
}


@pytest.mark.parametrize("deployment", sorted(DEPLOYMENTS))
def test_sweep_results_bitwise_equal_to_two_cumsums(deployment):
    """Every ``ScenarioResult`` output and counter of the batched sweep
    is what the two-cumsum plan gives."""
    specs = [ScenarioSpec(days=0.25, n_files=20_000, seed=seed,
                          cache_tb=10.0, **DEPLOYMENTS[deployment])
             for seed in (11, 12)]

    def sweep():
        res = run_sweep(specs, backend="jax", tick=60.0)
        assert res.ok and len(res.results) == len(specs)
        return [{k: v for k, v in dataclasses.asdict(r).items()
                 if k != "wall_s"} for r in res.results]

    old = _with_plan(_two_cumsum_plan, sweep)
    new = _with_plan(_migration_plan, sweep)
    assert new == old
    migrated = [r["metrics"]["disk_to_gcs_pb"] > 0 for r in new]
    assert all(migrated) == (deployment != "cfgII")


@pytest.mark.parametrize("n_files", [20_000, 1000],
                         ids=["jnp-20000", "jnp-1000"])
def test_program_with_a_busy_link_queue_bitwise_equal(n_files):
    """With two slots on each disk->cloud link the migrations queue on
    most ticks; every lane output, series included, is the two-cumsum
    plan's, with the file axis in 80 blocks of the prefix count (20,000
    files) and in 4 (1000 files)."""
    specs = [ScenarioSpec(base="III", days=0.1, n_files=n_files, seed=seed,
                          cache_tb=1.0) for seed in (3, 4)]
    grid = pack_specs(specs, tick=60.0)
    slots = np.asarray(grid.link_slots).copy()
    slots[:, 2::3] = 2.0  # link 3 * site + 2 is the site's disk->cloud
    grid = dataclasses.replace(grid, link_slots=slots)

    def run():
        return simulate_packed(grid, record_series=True)

    old = _with_plan(_two_cumsum_plan, run)
    new = _with_plan(_migration_plan, run)
    assert (new["ser_link"][..., 2] == 2).sum() > 10  # the link was full
    assert set(new) == set(old)
    for key in new:
        np.testing.assert_array_equal(new[key], old[key], err_msg=key)
