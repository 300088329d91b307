"""Cross-validation of the batched (jax) sweep backend against the
event-driven reference engine.

The two engines share catalogue and job-arrival randomness draw-for-draw
but differ in clocking (fixed tick vs. event jumps) and in the per-job
selection/duration stream interleaving, so agreement is statistical: the
per-lane tolerance is the paper's Table 2 validation tolerance (5%), the
same bar the reference engine itself is held to against the paper.
"""

import jax
import numpy as np
import pytest

from repro.core.hcdc import HCDCScenario
from repro.core.scenarios import (
    ScenarioSpec,
    build_config,
    expand_grid,
    pack_specs,
    with_seeds,
)
from repro.sim import batched
from repro.sim.batched import (
    _BIG_TICKET,
    WAIT_ADMITS_PER_TICK,
    _queue_heads,
    simulate_packed,
)
from repro.sim.sweep import run_sweep

# Table 2 validation tolerance (fractional): the §4.2 bar for "the
# simulation reproduces the system" — reused as the per-lane parity bar.
TOL = 0.05

TINY = dict(days=0.25, n_files=1000)
QUICK = dict(days=0.1, n_files=1000)


def _close(a, b, tol=TOL, floor=1.0):
    return abs(a - b) <= tol * max(abs(a), abs(b), floor)


def _assert_lane_parity(ref, jx, tol=TOL):
    assert len(ref.results) == len(jx.results)
    for a, b in zip(ref.results, jx.results):
        assert b.spec == a.spec
        lbl = a.spec.label
        # A capacity-constrained cold tier amplifies realization noise:
        # *which* few files land in the small GCS window decides the
        # recall (egress) volume, so the cost bar doubles there.
        cost_tol = tol if a.spec.gcs_limit_tb is None or \
            a.spec.gcs_limit_tb == float("inf") else 2 * tol
        assert _close(a.jobs_done, b.jobs_done, tol), \
            f"{lbl}: jobs_done {a.jobs_done} vs {b.jobs_done}"
        assert _close(a.cost_usd, b.cost_usd, cost_tol), \
            f"{lbl}: cost {a.cost_usd} vs {b.cost_usd}"
        assert _close(a.metrics["download_pb"], b.metrics["download_pb"],
                      tol, floor=1e-6), f"{lbl}: download_pb"
        assert abs(a.metrics["jobs_submitted"]
                   - b.metrics["jobs_submitted"]) <= 3, \
            f"{lbl}: jobs_submitted"
        assert abs(a.metrics["job_waiting_h_mean"]
                   - b.metrics["job_waiting_h_mean"]) <= 0.05, \
            f"{lbl}: job_waiting_h_mean"


# ------------------------------------------------------------------ packing
def test_pack_specs_replicates_reference_catalogue():
    """The packed sizes/popularity replicate the event engine's host RNG
    draws bit-for-bit (modulo the f32 cast)."""
    spec = ScenarioSpec(base="III", cache_tb=20.0, seed=3, **TINY)
    grid = pack_specs([spec])
    sc = HCDCScenario(build_config(spec))
    for si, st in enumerate(sc.sites):
        np.testing.assert_allclose(grid.sizes[0, si], st.sizes, rtol=1e-6)
        np.testing.assert_array_equal(grid.pop[0, si], st.pop)
    assert grid.n_jobs[0].sum() > 0


def test_pack_specs_deduplicates_pricing_lanes():
    specs = expand_grid({
        "base": "III", "cache_tb": [10.0, 20.0],
        "egress": ["internet", "direct", "interconnect"],
        "storage_price": [None, 0.02], **TINY,
    })
    grid = pack_specs(specs)
    assert grid.n_specs == 12
    assert grid.n_lanes == 2  # only cache_tb changes the dynamics
    assert sorted(set(grid.lane_of.tolist())) == [0, 1]
    # every spec keeps its own cost model
    assert len(grid.cost_models) == 12


def test_pack_specs_workload_gets_own_dynamics_lane():
    """Workload reshapes the simulated job stream, so workload-only
    variants must NOT share a lane — unlike pricing-only variants."""
    specs = expand_grid({
        "base": "III", "cache_tb": 15.0,
        "workload": ["steady", "diurnal:amplitude=0.8"],
        "egress": ["internet", "direct"], **TINY,
    })
    grid = pack_specs(specs)
    assert grid.n_specs == 4
    assert grid.n_lanes == 2  # workload splits, egress does not
    # the compiled schedule is exported per lane: steady is exactly ones,
    # the diurnal lane is mean-preserving but non-constant
    steady_lane = int(grid.lane_of[specs.index(next(
        s for s in specs if s.workload == "steady"))])
    assert (grid.rate_mult[steady_lane] == 1.0).all()
    # (the 0.25-day horizon covers the rising quarter of the default
    # 24 h diurnal period, so the lane is >= 1 but clearly non-constant)
    other = grid.rate_mult[1 - steady_lane]
    assert other.max() > 1.5 and other.max() > other.min()
    # modulated lanes still carry jobs
    assert (grid.n_jobs > 0).all()


def test_pack_specs_rejects_nonuniform_and_curves():
    with pytest.raises(ValueError, match="uniform 'days'"):
        pack_specs([ScenarioSpec(days=0.25, n_files=100),
                    ScenarioSpec(days=0.5, n_files=100)])
    with pytest.raises(ValueError, match="uniform 'n_files'"):
        pack_specs([ScenarioSpec(days=0.25, n_files=100),
                    ScenarioSpec(days=0.25, n_files=200)])
    with pytest.raises(ValueError, match="curves"):
        pack_specs([ScenarioSpec(days=0.25, n_files=100, curves=True)])
    with pytest.raises(ValueError, match="tick"):
        pack_specs([ScenarioSpec(days=0.25, n_files=100)], tick=0.0)


def test_run_sweep_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        run_sweep([ScenarioSpec(**TINY)], backend="fortran")


# ------------------------------------------------- waiting-queue heads
def _ticket_plane(rng, shape, n_wait, low=0):
    """A ticket plane as the tick builds it: ``n_wait`` distinct tickets in
    ``[low, 2**30)`` at random files of each row, ``_BIG_TICKET`` elsewhere."""
    t = np.full(shape, int(_BIG_TICKET), np.int32)
    for row in t.reshape(-1, shape[-1]):
        at = rng.choice(shape[-1], n_wait, replace=False)
        row[at] = low + rng.choice(2 ** 30 - low, n_wait, replace=False)
    return t


W = WAIT_ADMITS_PER_TICK
F_HEADS = 4096
HEAD_CASES = {
    "no_waiters": lambda r: _ticket_plane(r, (2, F_HEADS), 0),
    "one_waiter": lambda r: _ticket_plane(r, (2, F_HEADS), 1),
    "w_minus_1_waiters": lambda r: _ticket_plane(r, (2, F_HEADS), W - 1),
    "w_waiters": lambda r: _ticket_plane(r, (2, F_HEADS), W),
    "many_waiters": lambda r: _ticket_plane(r, (2, F_HEADS), 1500),
    "every_file_waits": lambda r: _ticket_plane(r, (2, F_HEADS), F_HEADS),
    "rows_differ": lambda r: np.concatenate(
        [_ticket_plane(r, (1, F_HEADS), n) for n in (0, 2, W, 300)]),
    # every ticket of [2**30 - 3000, 2**30 - 1], the largest one included
    "tickets_below_big": lambda r: _ticket_plane(
        r, (2, F_HEADS), 3000, low=2 ** 30 - 3000),
    "equal_tickets": lambda r: np.where(
        r.random((2, F_HEADS)) < 0.01, 7, int(_BIG_TICKET)).astype(np.int32),
    "lanes_vmapped": lambda r: _ticket_plane(r, (3, 2, F_HEADS), 9),
}


@pytest.mark.parametrize("case", sorted(HEAD_CASES))
def test_queue_heads_equal_top_k(case):
    """The W masked argmin passes give what ``top_k(-tickets, W)`` gives,
    values and indices bitwise, empty slots and equal keys included."""
    t = HEAD_CASES[case](np.random.default_rng(0))
    assert t.dtype == np.int32 and t.max() <= int(_BIG_TICKET)
    if t.ndim == 3:  # [lanes, sites, files], as the grid program runs it
        got = jax.vmap(lambda x: _queue_heads(x, W))(t)
    else:
        got = jax.jit(_queue_heads, static_argnums=1)(t, W)
    want = jax.lax.top_k(-t, W)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


#: The benchmark's three deployments, cut to 1000 files and a 1 TB disk
#: per site: cfg III, cfg II, and cfg III under a bucket quota, here
#: 1 TB, so that it binds as the gcs20 cell's 20 TB does.
QUEUE_DEPLOYMENTS = {
    "cfgIII": dict(base="III"),
    "cfgII": dict(base="II"),
    "gcs20": dict(base="III", gcs_limit_tb=1.0),
}


@pytest.mark.parametrize("deployment", sorted(QUEUE_DEPLOYMENTS))
def test_queue_heads_program_bitwise_equal_to_top_k(monkeypatch,
                                                    deployment):
    """The whole grid program, with its queue heads taken by ``top_k``
    and by ``_queue_heads``, gives bitwise equal per-lane outputs on a
    small disk whose waiting queue runs far deeper than W."""
    specs = with_seeds([ScenarioSpec(cache_tb=1.0, **QUICK,
                                     **QUEUE_DEPLOYMENTS[deployment])], 2)
    grid = pack_specs(specs, tick=60.0)
    batched._grid_program.cache_clear()
    try:
        with monkeypatch.context() as m:
            m.setattr(batched, "_queue_heads",
                      lambda t, w: jax.lax.top_k(-t, w))
            by_top_k = simulate_packed(grid, record_series=True)
        batched._grid_program.cache_clear()
        heads = simulate_packed(grid, record_series=True)
    finally:
        batched._grid_program.cache_clear()
    assert heads["ser_queue"].max() > 10 * W
    if deployment == "gcs20":
        assert (heads["gcs_refused_ticks"] > 0).all()  # the quota binds
    assert set(heads) == set(by_top_k)
    for key in heads:
        np.testing.assert_array_equal(heads[key], by_top_k[key],
                                      err_msg=key)


def test_benchmark_names_the_sweeps_program():
    """The chip benchmark builds its programs as
    ``_grid_program(S, K, n_months, resolve_tick_impl("auto").name,
    None)``: that is the cache entry a plain ``simulate_packed`` call
    uses, so a program the benchmark compiles is the one a request runs.
    No other implementation name is accepted."""
    from repro.kernels.registry import resolve_tick_impl

    grid = pack_specs([ScenarioSpec(**QUICK)], tick=60.0)
    S, K = len(grid.site_names), grid.max_jobs_per_tick
    batched._grid_program.cache_clear()
    try:
        simulate_packed(grid)
        assert batched._grid_program.cache_info().currsize == 1
        name = resolve_tick_impl("auto").name
        assert name == resolve_tick_impl("jnp").name == "jnp"
        batched._grid_program(S, K, grid.n_months, name, None)
        info = batched._grid_program.cache_info()
        assert (info.hits, info.currsize) == (1, 1)
    finally:
        batched._grid_program.cache_clear()
    for other in ("pallas", "pallas_interpret", "cuda"):
        with pytest.raises(ValueError, match="tick implementation"):
            resolve_tick_impl(other)
        with pytest.raises(ValueError, match="tick implementation"):
            batched._grid_program.__wrapped__(S, K, grid.n_months, other)


# ------------------------------------------- lane chunking & shape buckets
def test_lane_chunked_bitwise_identical():
    """Chunked execution (ISSUE 4) splits lanes into fixed-size padded
    chunks; lanes never interact, so per-lane results must be *bitwise*
    identical to the unchunked run — including the odd-size last chunk."""
    specs = expand_grid({
        "base": "III", "cache_tb": [10.0, 15.0, 20.0, 25.0, 30.0],
        "seed": 7, **TINY,
    })
    whole = run_sweep(specs, backend="jax", tick=60.0)
    chunked = run_sweep(specs, backend="jax", tick=60.0, lane_chunk=2)
    for a, b in zip(whole.results, chunked.results):
        assert a.spec == b.spec
        assert a.metrics == b.metrics, a.spec.label
        assert a.cost_usd == b.cost_usd


def test_bucket_padding_bitwise_unchanged():
    """Rounding K/J up to power-of-two buckets (compile-cache stability)
    only adds window slots the validity mask rejects and job rows that
    never submit — every raw per-lane aggregate stays bitwise equal."""
    specs = expand_grid({"base": "III", "cache_tb": [15.0, 30.0], **TINY})
    bucketed = pack_specs(specs, tick=60.0)
    exact = pack_specs(specs, tick=60.0, bucket=False)
    # the bench/test catalogue is non-degenerate: bucketing actually pads
    assert bucketed.max_jobs_per_tick >= exact.max_jobs_per_tick
    assert bucketed.job_fid.shape[2] >= exact.job_fid.shape[2]
    assert bucketed.max_jobs_per_tick & (bucketed.max_jobs_per_tick - 1) == 0
    assert bucketed.job_fid.shape[2] & (bucketed.job_fid.shape[2] - 1) == 0
    out_b = simulate_packed(bucketed)
    out_e = simulate_packed(exact)
    assert set(out_b) == set(out_e)
    for key in out_e:
        if key in ("download_b", "wait_h_sum"):
            # f32 sums over the padded J axis: identical addends (padding
            # contributes exact zeros) but a different reduction-tree
            # shape — equal to summation-order ulp, not bitwise.
            np.testing.assert_allclose(out_b[key], out_e[key], rtol=1e-6,
                                       err_msg=key)
        else:
            np.testing.assert_array_equal(out_b[key], out_e[key],
                                          err_msg=key)


def test_shard_map_bitwise_identical():
    """``shard=True`` (ISSUE 10) runs each lane batch as one
    ``jax.shard_map`` program over the local device mesh; lane programs
    exchange no collectives, so per-lane results — including lanes
    replicated to pad the batch to a mesh multiple — must be *bitwise*
    identical to the per-chunk Python loop."""
    specs = expand_grid({
        "base": "III", "cache_tb": [10.0, 15.0, 20.0], "seed": 7, **TINY,
    })
    grid = pack_specs(specs, tick=60.0)
    plain = simulate_packed(grid)
    sharded = simulate_packed(grid, shard=True)
    assert set(plain) == set(sharded)
    for key in plain:
        np.testing.assert_array_equal(plain[key], sharded[key],
                                      err_msg=key)
    # chunked + sharded: chunk size rounds up to a mesh multiple
    chunked = simulate_packed(grid, lane_chunk=2, shard=True)
    for key in plain:
        np.testing.assert_array_equal(plain[key], chunked[key],
                                      err_msg=key)


def test_shard_map_bitwise_identical_on_four_devices():
    """``shard=True`` over 4 (virtual CPU) devices: the scan carry and
    the cloud gate's loop carry must vary over the lane axis like the
    tick's output, and per-lane results must equal the unsharded
    program's bitwise, whole and chunked, with a lane whose bucket quota
    binds. Runs in a subprocess so the forced device count does not leak
    into this test session."""
    import os
    import subprocess
    import sys
    import textwrap

    prog = textwrap.dedent("""
        import jax, numpy as np
        from repro.core.scenarios import (ScenarioSpec, expand_grid,
                                          pack_specs)
        from repro.sim.batched import simulate_packed

        assert len(jax.devices()) == 4, jax.devices()
        specs = expand_grid({"base": "III", "cache_tb": [10.0, 15.0, 20.0],
                             "seed": 7, "days": 0.05, "n_files": 300})
        specs.append(ScenarioSpec(base="III", cache_tb=0.5, gcs_limit_tb=0.1,
                                  seed=7, days=0.05, n_files=300))
        grid = pack_specs(specs, tick=60.0)
        plain = simulate_packed(grid)
        for chunk in (None, 2):
            sharded = simulate_packed(grid, shard=True, lane_chunk=chunk)
            assert set(sharded) == set(plain)
            for key in plain:
                np.testing.assert_array_equal(plain[key], sharded[key],
                                              err_msg=key)
        assert plain["gcs_refused_ticks"][-1] > 0  # the quota binds
        assert plain["gcs_gate_passes"][-1] > 1
        print("OK")
    """)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=600, env=env)
    assert "OK" in res.stdout, res.stderr[-3000:]


def test_jax_transport_refused_on_accelerator(monkeypatch):
    """Fleet workers pin JAX to the CPU and a chip belongs to one
    process, so on an accelerator host ``backend="jax"`` with a
    ``transport`` raises (pointing at ``shard=True``) instead of quietly
    simulating on the host CPU. The process backend's fleet is fine."""
    from repro.sim import sweep
    from repro.sim.sweep import SweepDriver

    monkeypatch.setattr(sweep, "_on_accelerator", lambda: True)
    spec = ScenarioSpec(days=0.05, n_files=300)
    for transport in ("local", "subprocess"):
        with pytest.raises(ValueError, match="shard=True"):
            run_sweep([spec], backend="jax", transport=transport)
        with pytest.raises(ValueError, match="fleet workers run on the CPU"):
            SweepDriver(backend="jax", transport=transport)
    assert run_sweep([spec], backend="process", transport="local",
                     workers=1).ok
    monkeypatch.setattr(sweep, "_on_accelerator", lambda: False)
    SweepDriver(backend="jax", transport="local")  # CPU host: allowed


def test_shard_excludes_devices_round_robin():
    import jax

    spec = ScenarioSpec(**TINY)
    grid = pack_specs([spec], tick=60.0)
    with pytest.raises(ValueError, match="shard"):
        simulate_packed(grid, shard=True, devices=jax.devices())


def test_lane_chunk_knob_validation():
    with pytest.raises(ValueError, match="lane_chunk"):
        run_sweep([ScenarioSpec(**TINY)], backend="jax", lane_chunk=0)
    with pytest.raises(ValueError, match="jax"):
        run_sweep([ScenarioSpec(**TINY)], backend="process", lane_chunk=4)
    with pytest.raises(ValueError, match="jax"):
        run_sweep([ScenarioSpec(**TINY)], backend="process", devices=[])
    with pytest.raises(ValueError, match="devices"):
        run_sweep([ScenarioSpec(**TINY)], backend="jax", devices=[])


def test_pack_specs_memoizes_catalogue_draws():
    """Lanes differing only in capacity limits replicate the same RNG
    stream, so the packed catalogue/job arrays must be identical (drawn
    once, shared) while capacity arrays still differ per lane."""
    specs = expand_grid({
        "base": "III", "cache_tb": [10.0, 20.0], "gcs_limit_tb": [None, 5.0],
        **TINY,
    })
    grid = pack_specs(specs)
    assert grid.n_lanes == 4
    for li in range(1, grid.n_lanes):
        np.testing.assert_array_equal(grid.sizes[0], grid.sizes[li])
        np.testing.assert_array_equal(grid.pop[0], grid.pop[li])
        np.testing.assert_array_equal(grid.job_fid[0], grid.job_fid[li])
        np.testing.assert_array_equal(grid.job_tail[0], grid.job_tail[li])
    assert len({tuple(r) for r in grid.disk_limit[:, :1].tolist()}) == 2


# ------------------------------------------------- reference cross-checks
@pytest.fixture(scope="module")
def small_grid():
    """8 dynamics lanes x pricing variants, covering cfg I/II/III, limited
    and unlimited tiers, both egress families."""
    specs = (expand_grid({
        "base": "III", "cache_tb": [10.0, 25.0, 60.0],
        "egress": ["internet", "direct"], "seed": 1, **TINY,
    }) + [
        ScenarioSpec(base="I", seed=2, **TINY),
        ScenarioSpec(base="II", seed=2, **TINY),
        ScenarioSpec(base="III", cache_tb=15.0, gcs_limit_tb=5.0,
                     seed=3, **TINY),
        ScenarioSpec(base="III", cache_tb=15.0, job_rate_scale=1.5,
                     seed=4, **TINY),
        ScenarioSpec(base="III", cache_tb=15.0, storage_price=0.02,
                     seed=4, **TINY),
    ])
    ref = run_sweep(specs, workers=2)
    jx = run_sweep(specs, backend="jax")
    return ref, jx


def test_jax_backend_matches_reference_per_lane(small_grid):
    ref, jx = small_grid
    _assert_lane_parity(ref, jx)


def test_jax_backend_volume_metrics_track_reference(small_grid):
    ref, jx = small_grid
    for a, b in zip(ref.results, jx.results):
        for key in ("gcs_to_disk_pb", "disk_to_gcs_pb", "gcs_used_pb"):
            assert _close(a.metrics[key], b.metrics[key], 2 * TOL,
                          floor=1e-4), f"{a.spec.label}: {key}"


def test_jax_backend_respects_config_structure(small_grid):
    _, jx = small_grid
    by_label = {r.spec.label: r for r in jx.results}
    cfg1 = next(r for r in jx.results if r.spec.base == "I")
    cfg2 = next(r for r in jx.results if r.spec.base == "II")
    assert cfg1.metrics["gcs_used_pb"] == 0.0
    assert cfg1.cost_usd == 0.0
    assert cfg2.metrics["gcs_to_disk_pb"] == 0.0
    limited = next(r for r in jx.results if r.spec.gcs_limit_tb == 5.0)
    assert limited.metrics["gcs_used_pb"] <= 5.0e12 / 1e15 + 1e-9
    # pricing-only variants share dynamics, not bills
    a = by_label["cfgIII,cache=10TB,egress=internet,seed=1"]
    b = by_label["cfgIII,cache=10TB,egress=direct,seed=1"]
    assert a.metrics["jobs_done"] == b.metrics["jobs_done"]
    assert a.metrics["gcs_to_disk_pb"] == b.metrics["gcs_to_disk_pb"]
    assert b.network_usd < a.network_usd


def test_jax_backend_deterministic(small_grid):
    """Same spec batch twice -> bitwise-identical results. (Different batch
    *shapes* may differ in the last float ulp: XLA reduction order.)"""
    _, jx = small_grid
    specs = [r.spec for r in jx.results][:4]
    once = run_sweep(specs, backend="jax")
    again = run_sweep(specs, backend="jax")
    for a, b in zip(once.results, again.results):
        assert a.metrics == b.metrics
        assert a.cost_usd == b.cost_usd


def test_jax_backend_tick_coarsening_stays_close(small_grid):
    """A coarser clock (30/60 s vs the 10 s generator interval) shifts
    event times by at most one tick; totals must stay within the parity
    bar. 60 s is the tick ``benchmarks/bench_sweep.py`` runs at."""
    _, jx = small_grid
    specs = [r.spec for r in jx.results]
    for tick, jobs_tol, cost_tol in ((30.0, 0.02, 0.04), (60.0, 0.02, 0.05)):
        coarse = run_sweep(specs, backend="jax", tick=tick)
        for a, b in zip(jx.results, coarse.results):
            assert _close(a.jobs_done, b.jobs_done, jobs_tol), \
                f"tick={tick}: {a.spec.label}"
            assert _close(a.cost_usd, b.cost_usd, cost_tol), \
                f"tick={tick}: {a.spec.label}"


# ------------------------------------------------------- workload parity
@pytest.fixture(scope="module")
def workload_grid(tmp_path_factory):
    """One spec per workload model (incl. a CSV trace), both backends."""
    trace = tmp_path_factory.mktemp("wl") / "trace.csv"
    trace.write_text("time_s,rate_mult\n0,1.5\n7200,0.5\n14400,2.0\n")
    wls = [
        "steady",
        "diurnal:amplitude=0.8,period_h=3",
        "campaign:period_h=2,duty=0.25,peak=2.5,off=0.5",
        "zipf-drift:power_end=1.5,steps=4",
        f"trace:{trace}",
    ]
    specs = [ScenarioSpec(base="III", cache_tb=15.0, seed=0, workload=w,
                          **TINY) for w in wls]
    ref = run_sweep(specs, workers=2)
    jx = run_sweep(specs, backend="jax")
    return ref, jx


def test_workload_models_match_reference_per_lane(workload_grid):
    """Every workload model agrees across backends: jobs at the Table 2
    bar; cost at the doubled bar, because at this 0.25-day quick-test
    horizon the reference engine's own cost realization noise is ~±6%
    (see the acceptance-grid note below) and rate modulation churns the
    cache harder. The slow 0.75-day test below applies the full 5% bar."""
    ref, jx = workload_grid
    for a, b in zip(ref.results, jx.results):
        lbl = a.spec.label
        assert _close(a.jobs_done, b.jobs_done, TOL), \
            f"{lbl}: jobs_done {a.jobs_done} vs {b.jobs_done}"
        assert _close(a.cost_usd, b.cost_usd, 2 * TOL), \
            f"{lbl}: cost {a.cost_usd} vs {b.cost_usd}"
        assert _close(a.metrics["download_pb"], b.metrics["download_pb"],
                      TOL, floor=1e-6), f"{lbl}: download_pb"


@pytest.mark.slow
def test_workload_models_acceptance_full_bar(tmp_path):
    """ISSUE 3 acceptance: per-lane jobs-done and bill totals for every
    workload model match across backends within the Table 2 5% tolerance
    (0.75-day horizon, where reference realization noise is ~±2%)."""
    trace = tmp_path / "trace.csv"
    trace.write_text("time_s,rate_mult\n0,1.5\n21600,0.5\n43200,2.0\n")
    wls = [
        "steady",
        "diurnal:amplitude=0.8,period_h=3",
        "campaign:period_h=2,duty=0.25,peak=2.5,off=0.5",
        "zipf-drift:power_end=1.5,steps=4",
        f"trace:{trace}",
    ]
    specs = [ScenarioSpec(base="III", cache_tb=15.0, seed=0, workload=w,
                          days=0.75, n_files=1000) for w in wls]
    ref = run_sweep(specs, workers=2)
    jx = run_sweep(specs, backend="jax")
    _assert_lane_parity(ref, jx)


def test_workload_job_streams_identical_across_backends(workload_grid):
    """Both backends derive the arrival stream from the same modulated
    count draws, so submissions match exactly, not just statistically."""
    ref, jx = workload_grid
    for a, b in zip(ref.results, jx.results):
        assert a.metrics["jobs_submitted"] == b.metrics["jobs_submitted"], \
            a.spec.workload


def test_workload_shapes_move_the_observables(workload_grid):
    """The axis actually does something: the trace's long-run mean is 4/3
    (1.5/0.5/2.0 over equal thirds), while the mean-1 shapes (diurnal and
    campaign over whole periods, rate-neutral zipf drift) keep the total."""
    ref, _ = workload_grid
    by = {r.spec.workload.partition(":")[0]: r for r in ref.results}
    steady = by["steady"].metrics["jobs_submitted"]
    assert by["trace"].metrics["jobs_submitted"] > 1.2 * steady
    assert by["zipf-drift"].metrics["jobs_submitted"] == steady
    assert by["campaign"].metrics["jobs_submitted"] == \
        pytest.approx(steady, rel=0.05)


# ------------------------------------------- acceptance grid (64 configs)
@pytest.mark.slow
def test_jax_backend_matches_reference_64_config_grid():
    """ISSUE 2 acceptance: a >= 64-config grid agrees with the process
    backend per lane within the Table 2 tolerance for jobs done and the
    monthly-bill total.

    Horizon note: at 0.25 simulated days the *reference engine's own*
    seed-to-seed cost spread is ~±6% (recall volume on a churning cache is
    the noisiest observable), so a 5% per-lane bar is only meaningful once
    the horizon averages that noise down — 0.75 days brings it to ~±2%.
    """
    specs = with_seeds(expand_grid({
        "base": "III",
        "cache_tb": [10.0, 20.0, 40.0, 80.0],
        "egress": ["internet", "direct"],
        "storage_price": [None, 0.02],
        "days": 0.75, "n_files": 1000,
    }), 4)
    assert len(specs) == 64
    ref = run_sweep(specs, workers=2)
    jx = run_sweep(specs, backend="jax")
    _assert_lane_parity(ref, jx)


# ------------------------------------------------- series capture (ISSUE 8)
def test_record_series_off_is_bitwise_identical():
    """Capture off must trace the exact pre-capture program: every
    original output key is bitwise equal with and without capture, and
    the series buffers appear only when capture is on."""
    specs = with_seeds([ScenarioSpec(base="III", cache_tb=15.0, **QUICK)], 2)
    grid = pack_specs(specs, tick=60.0)
    plain = simulate_packed(grid)
    rec = simulate_packed(grid, record_series=6)
    assert not any(k.startswith("ser_") for k in plain)
    for k in plain:
        np.testing.assert_array_equal(plain[k], rec[k], err_msg=k)
    for k in ("ser_disk", "ser_gcs", "ser_queue", "ser_run", "ser_link"):
        assert k in rec


def test_record_series_chunked_matches_unchunked():
    specs = with_seeds([ScenarioSpec(base="III", cache_tb=15.0, **QUICK)], 2)
    grid = pack_specs(specs, tick=60.0)
    whole = simulate_packed(grid, record_series=6)
    chunked = simulate_packed(grid, record_series=6, lane_chunk=1)
    for k in whole:
        np.testing.assert_array_equal(whole[k], chunked[k], err_msg=k)


def test_record_series_validation():
    from repro.sim.batched import series_from_capture

    spec = ScenarioSpec(base="III", cache_tb=15.0, **QUICK)
    grid = pack_specs([spec], tick=60.0)
    with pytest.raises(ValueError, match="record_series"):
        simulate_packed(grid, record_series=0)
    out = simulate_packed(grid)  # capture off
    with pytest.raises(ValueError, match="record_series"):
        series_from_capture(grid, out, 0, None)
    with pytest.raises(KeyError, match="series buffers"):
        series_from_capture(grid, out, 0, 6)
    with pytest.raises(ValueError, match="record_series"):
        run_sweep([spec], backend="process", record_series=6)


def test_series_from_capture_schema():
    """Stride, sample count, names, and the ``TimeSeries`` conversion."""
    from repro.sim.batched import LINK_TYPES, series_from_capture

    spec = ScenarioSpec(base="III", cache_tb=15.0, seed=3, **QUICK)
    grid = pack_specs([spec], tick=60.0)
    stride = 7  # deliberately not dividing n_ticks
    out = simulate_packed(grid, record_series=stride)
    n_samples = (grid.n_ticks - 1) // stride + 1
    series = series_from_capture(grid, out, 0, stride)
    expect = {"gcs_used"}
    for name in grid.site_names:
        expect.add(f"{name}.disk_used")
        expect.add(f"{name}.running_jobs")
        expect.add(f"{name}.wait_queue")
        expect.update(f"{name}.link_active.{lk}" for lk in LINK_TYPES)
    assert set(series) == expect
    times = np.asarray(grid.times)[::stride]
    for name, ts in series.items():
        assert len(ts.times) == len(ts.values) == n_samples, name
        np.testing.assert_allclose(ts.times, times)
        assert min(ts.values) >= 0.0, name
    assert max(series[f"{grid.site_names[0]}.running_jobs"].values) > 0


def test_series_parity_with_event_engine():
    """Cross-backend series parity: the time-averaged occupancy and
    running-jobs series agree within the Table 2 bar (5%) on a 0.75-day
    horizon (the horizon that averages realization noise below the bar —
    see the 64-config grid's note). Point-sample extremes (``max``) stay
    unasserted: *when* the peak lands differs between the clocking
    models by design."""
    import dataclasses

    horizon = dict(days=0.75, n_files=1000)
    base_specs = [
        ScenarioSpec(base="III", cache_tb=15.0, seed=3, **horizon),
        ScenarioSpec(base="II", seed=2, **horizon),
    ]
    curve_specs = [dataclasses.replace(s, curves=True) for s in base_specs]
    ref = run_sweep(curve_specs, workers=2)
    jx = run_sweep(base_specs, backend="jax", record_series=360)
    for a, b in zip(ref.results, jx.results):
        assert a.series and b.series
        common = set(a.series) & set(b.series)
        # both backends record occupancy + running jobs under one schema
        assert {"gcs_used"} | {
            f"{s}.{k}" for s in ("Site-1", "Site-2")
            for k in ("disk_used", "running_jobs")} <= common
        for name in sorted(common):
            sa, sb = a.series[name], b.series[name]
            assert sa["n"] == sb["n"], name
            assert _close(sa["mean"], sb["mean"], TOL), \
                f"{a.spec.label}: {name} mean {sa['mean']} vs {sb['mean']}"


def test_run_sweep_jax_attaches_series_digests():
    specs = with_seeds([ScenarioSpec(base="III", cache_tb=15.0, **QUICK)], 2)
    plain = run_sweep(specs, backend="jax")
    rec = run_sweep(specs, backend="jax", record_series=6)
    assert all(not r.series for r in plain.results)
    for a, b in zip(plain.results, rec.results):
        assert b.series and "gcs_used" in b.series
        assert set(b.series["gcs_used"]) == {"n", "min", "mean", "max",
                                             "last"}
        # attaching digests must not perturb the simulation itself
        assert a.metrics == b.metrics
        assert a.cost_usd == b.cost_usd
