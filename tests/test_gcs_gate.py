"""The batched engine's shared cloud admission gate is first fit.

The event engine scans a site's evicted files in order and migrates each
one that fits in the bucket on its own ("cold tier full; retry next
tick"). ``repro.sim.batched._gcs_first_fit`` runs the same rule as
prefix-sum passes over the site-major flattened candidate vector. These
tests hold it to a plain first-fit loop bit for bit, to the three-pass
gate it replaced where that gate was exact (an unlimited or a disabled
bucket), and hold the program under a binding quota to the event engine.
"""

import jax
import numpy as np
import pytest

from repro.core.scenarios import ScenarioSpec, pack_specs, with_seeds
from repro.obs.metrics import get_registry
from repro.sim import batched
from repro.sim.batched import _gcs_first_fit, simulate_packed
from repro.sim.sweep import run_sweep

MiB = 2.0 ** 20
TB = 1e12
#: Quotas below 2**44 bytes keep every sum the gate forms of whole-MiB
#: sizes exact in float32, so its result does not depend on the order of
#: summation and a plain loop can be compared with it bit for bit.
QUOTA = 10 * 2.0 ** 40


def _table3_sizes(rng, n):
    """Table 3 file sizes (exponential, 0.026 per GiB, clipped to
    [9.76 MB, 134 GB]) rounded up to whole MiB."""
    size = np.clip(rng.exponential(1.0 / 0.026, n) * 2.0 ** 30, 9.76e6,
                   134e9)
    return (np.ceil(size / MiB) * MiB).astype(np.float32)


def _plane(rng, n=10_000, share=0.3):
    return rng.random(n) < share, _table3_sizes(rng, n)


def _blockers(rng):
    """Candidates of 10 to 64 MiB with oversized ones among them: the
    room runs out mid-scan, and behind every blocker lies a file that
    still fits."""
    want = rng.random(10_000) < 0.5
    sizes = (rng.integers(10, 65, 10_000) * MiB).astype(np.float32)
    sizes[rng.random(10_000) < 0.05] = 512 * 2.0 ** 30
    return want, sizes, np.float32(QUOTA - 20 * 2.0 ** 30), np.float32(QUOTA)


GATE_CASES = {
    "oversized_blockers": _blockers,
    "one_tib_left": lambda r: (*_plane(r), np.float32(QUOTA - 2.0 ** 40),
                               np.float32(QUOTA)),
    "nearly_full": lambda r: (*_plane(r), np.float32(QUOTA - 100 * MiB),
                              np.float32(QUOTA)),
    "full": lambda r: (*_plane(r), np.float32(QUOTA), np.float32(QUOTA)),
    "no_candidates": lambda r: (np.zeros(10_000, bool),
                                _table3_sizes(r, 10_000),
                                np.float32(QUOTA / 2), np.float32(QUOTA)),
    # few enough candidates that all of them together stay under 2**44
    "unlimited": lambda r: (*_plane(r, share=0.03), np.float32(0.0),
                            np.float32(np.inf)),
    # cfg II: no tier, so no file is ever a migration candidate
    "disabled": lambda r: (np.zeros(10_000, bool), _table3_sizes(r, 10_000),
                           np.float32(0.0), np.float32(0.0)),
    "binding_quota": lambda r: _binding_quota(np.random.default_rng(7)),
}


def _binding_quota(rng):
    """The tick's ``[S, F]`` planes (S=4, F=33), scanned site-major:
    whole-MiB files up to about 1 GB and 18 GiB of room left, less than
    the candidates need, so the gate takes more than one pass."""
    want = rng.random((4, 33)) < 0.4
    sizes = (rng.integers(1, 954, (4, 33)) * MiB).astype(np.float32)
    return want, sizes, np.float32(2 * 2.0 ** 30), np.float32(20 * 2.0 ** 30)


def _plain_first_fit(want, sizes, used, limit):
    """Scan the candidates in order (site-major, for a ``[S, F]`` plane);
    admit each one that fits."""
    admitted = np.zeros(want.size, bool)
    used = float(used)
    for i in np.flatnonzero(want):
        if used + float(sizes.flat[i]) <= float(limit):
            admitted[i] = True
            used += float(sizes.flat[i])
    return admitted.reshape(want.shape), np.float32(used)


@pytest.mark.parametrize("case", sorted(GATE_CASES))
def test_gate_equals_plain_first_fit(case):
    want, sizes, used, limit = GATE_CASES[case](np.random.default_rng(3))
    admitted, used_out, passes = jax.jit(_gcs_first_fit)(want, sizes, used,
                                                         limit)
    plain, plain_used = _plain_first_fit(want, sizes, used, limit)
    np.testing.assert_array_equal(np.asarray(admitted), plain)
    assert np.asarray(used_out).tobytes() == plain_used.tobytes()
    assert used_out <= limit
    if case == "unlimited":
        assert int(passes) == 1 and (plain == want).all()
    elif case in ("no_candidates", "disabled", "full"):
        assert int(passes) == 0
    if case in ("oversized_blockers", "one_tib_left", "nearly_full",
                "binding_quota"):
        # the quota binds: something waits, and a later file went in past
        # the first one that did not fit
        refused = np.flatnonzero(want & ~plain)
        assert refused.size and plain.reshape(-1)[refused[0]:].any()
        assert int(passes) > (1 if case != "nearly_full" else 0)


def test_gate_lanes_are_independent():
    """Vmapped over lanes, as the grid program runs it: each lane takes
    its own passes and gives its own plain first fit."""
    rng = np.random.default_rng(5)
    cases = [GATE_CASES[c](rng) for c in ("oversized_blockers",
                                          "unlimited", "disabled",
                                          "one_tib_left")]
    want, sizes, used, limit = (np.stack(x) for x in zip(*cases))
    admitted, used_out, passes = jax.jit(jax.vmap(_gcs_first_fit))(
        want, sizes, used, limit)
    for li, case in enumerate(cases):
        plain, plain_used = _plain_first_fit(*case)
        np.testing.assert_array_equal(np.asarray(admitted[li]), plain)
        assert np.asarray(used_out[li]).tobytes() == plain_used.tobytes()
    assert [int(p) for p in passes[1:3]] == [1, 0]
    assert int(passes[0]) > 1 and int(passes[3]) > 1


def _three_pass_gate(want, sizes, used, limit):
    """The gate before first fit: three prefix-sum passes over the
    flattened planes, each over the candidates not yet admitted, a
    blocker left among them."""
    want_flat, sizes_flat = want.reshape(-1), sizes.reshape(-1)
    admitted = jax.numpy.zeros_like(want_flat)
    for _ in range(3):
        rem = want_flat & ~admitted
        csum = jax.numpy.cumsum(sizes_flat * rem)
        new = rem & (used + csum <= limit)
        used = used + jax.numpy.sum(sizes_flat * new)
        admitted = admitted | new
    return admitted.reshape(want.shape), used, jax.numpy.int32(3)


@pytest.mark.parametrize("case", ["unlimited", "disabled"])
def test_gate_equals_three_pass_gate_without_a_quota(case):
    """Float sizes as the catalogue draws them (not whole MiB): with no
    quota to bind, the first-fit gate's mask and bucket level are the
    three-pass gate's, bit for bit."""
    rng = np.random.default_rng(11)
    n, shape = 3, (3, 2, 5_000)  # lanes, sites, files
    want = rng.random(shape) < (0.3 if case == "unlimited" else 0.0)
    sizes = np.clip(rng.exponential(1.0 / 0.026, shape) * 2.0 ** 30,
                    9.76e6, 134e9).astype(np.float32)
    used = rng.uniform(0, 1e13, n).astype(np.float32)
    limit = np.full(n, np.inf if case == "unlimited" else 0.0, np.float32)
    if case == "disabled":
        used[:] = 0.0
    new = jax.jit(jax.vmap(_gcs_first_fit))(want, sizes, used, limit)
    old = jax.jit(jax.vmap(_three_pass_gate))(want, sizes, used, limit)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
    assert np.asarray(new[1]).tobytes() == np.asarray(old[1]).tobytes()
    expect = 1 if case == "unlimited" else 0
    assert [int(p) for p in new[2]] == [expect] * n


COUNTERS = ("gcs_gate_passes", "gcs_refused_ticks", "gcs_first_refusal_s")


def test_program_outputs_equal_three_pass_gate_without_a_quota(
        monkeypatch):
    """cfg III (unlimited bucket) and cfg II (no bucket): every lane
    output of the grid program, series included, but the gate's
    counters, is bitwise what the program with the three-pass gate
    gives."""
    specs = with_seeds([ScenarioSpec(base=b, cache_tb=1.0, days=0.1,
                                     n_files=1000) for b in ("III", "II")],
                       2)
    grid = pack_specs(specs, tick=60.0)
    batched._grid_program.cache_clear()
    try:
        with monkeypatch.context() as m:
            m.setattr(batched, "_gcs_first_fit", _three_pass_gate)
            old = simulate_packed(grid, record_series=True)
        batched._grid_program.cache_clear()
        new = simulate_packed(grid, record_series=True)
    finally:
        batched._grid_program.cache_clear()
    assert set(new) == set(old)
    assert new["diskgcs_b"][grid.gcs_enabled].min() > 0  # cfg III migrates
    for key in set(new) - set(COUNTERS):
        np.testing.assert_array_equal(new[key], old[key], err_msg=key)
    assert (new["gcs_refused_ticks"] == 0).all()
    assert np.isinf(new["gcs_first_refusal_s"]).all()
    passes = new["gcs_gate_passes"]
    assert (passes[~grid.gcs_enabled] == 0).all()
    assert (0 < passes[grid.gcs_enabled]).all()
    assert (passes <= grid.n_ticks).all()


# ----------------------------------------- cfg III under a 20 TB quota
#: ``benchmarks/chip/configs/hcdc-cfgIII-1M-gcs20.json`` at 20,000 files
#: per site: cfg III, a 10 TB disk per site, a 20 TB bucket, 0.25 days.
GCS20 = dict(base="III", cache_tb=10.0, gcs_limit_tb=20.0, days=0.25,
             n_files=20_000)


@pytest.fixture(scope="module")
def gcs20():
    specs = with_seeds([ScenarioSpec(**GCS20)], 2)
    ref = run_sweep(specs, workers=1)
    before = get_registry().value("sweep.jax.gcs_gate_passes")
    jx = run_sweep(specs, backend="jax", tick=60.0)
    jx.registry_passes = (get_registry().value("sweep.jax.gcs_gate_passes")
                          - before)
    grid = pack_specs(specs, tick=60.0)
    out = simulate_packed(grid, record_series=True)
    return ref, jx, grid, out


def test_gcs20_matches_event_engine(gcs20):
    """Per lane, at ``test_batched``'s bars: jobs, bytes downloaded, the
    mean wait, bytes migrated and kept in the bucket, and the storage
    and operations bills. The recall egress bill is not held per lane:
    under a full bucket the few files read back decide it, and it
    differs between the engines by tens of percent either way at both
    clocks (as ``benchmarks/chip/reference.py`` leaves ``recall_gap``
    and ``network_gap`` out of ``correct``)."""
    from test_batched import TOL, _close

    ref, jx, _, _ = gcs20
    assert len(ref.results) == len(jx.results)
    for a, b in zip(ref.results, jx.results):
        assert b.spec == a.spec
        lbl = a.spec.label
        assert _close(a.jobs_done, b.jobs_done), lbl
        assert _close(a.metrics["download_pb"], b.metrics["download_pb"],
                      floor=1e-6), lbl
        assert abs(a.metrics["jobs_submitted"]
                   - b.metrics["jobs_submitted"]) <= 3, lbl
        assert abs(a.metrics["job_waiting_h_mean"]
                   - b.metrics["job_waiting_h_mean"]) <= 0.05, lbl
        for key in ("disk_to_gcs_pb", "gcs_used_pb"):
            assert _close(a.metrics[key], b.metrics[key], floor=1e-4), \
                f"{lbl}: {key}"
        for key in ("storage_usd", "ops_usd"):
            assert _close(getattr(a, key), getattr(b, key), 2 * TOL,
                          floor=1e-3), f"{lbl}: {key}"


def test_gcs20_bucket_never_over_its_quota(gcs20):
    """The quota binds in every lane, and the bucket's level at every
    tick stays at most 20 TB, float32 rounding of the byte count aside."""
    _, jx, grid, out = gcs20
    quota = GCS20["gcs_limit_tb"] * TB
    level = out["ser_gcs"]
    assert level.shape == (grid.n_lanes, grid.n_ticks)
    assert level.max() <= quota * (1 + 2 ** -23)
    assert (level.max(axis=1) > 0.99 * quota).all()
    for li, r in enumerate(jx.results):
        c = r.counters
        assert c["gcs_refused_ticks"] > 0
        assert 0 < c["gcs_first_refusal_h"] < GCS20["days"] * 24
        assert c["gcs_gate_passes"] > 0
        # the series run is the same program with capture on
        assert c["gcs_gate_passes"] == out["gcs_gate_passes"][li]
        assert r.metrics["gcs_used_pb"] * 1e15 <= quota * (1 + 2 ** -23)
    assert jx.registry_passes == sum(r.counters["gcs_gate_passes"]
                                     for r in jx.results)
