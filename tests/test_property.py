"""Property-based tests (hypothesis) on system invariants."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="dev extra: pip install -r requirements-dev.txt")

from hypothesis import given, settings, strategies as st

from repro.core.carousel import SlidingWindow
from repro.sim.cloud import GCSCostModel
from repro.sim.distributions import (
    BoundedExponential,
    FractionalCounter,
    TruncatedNormalCount,
)
from repro.sim.infrastructure import GiB


@given(st.lists(st.floats(0, 100, allow_nan=False), min_size=1, max_size=500))
def test_fractional_counter_long_run_rate(xs):
    """Emitted integer total differs from the real-valued total by < 1."""
    c = FractionalCounter()
    emitted = sum(c.emit(x) for x in xs)
    assert abs(emitted - sum(xs)) < 1.0


@given(st.floats(0.001, 5.0), st.floats(0.0, 1.0), st.floats(1.5, 100.0),
       st.integers(0, 2**31 - 1))
def test_bounded_exponential_within_bounds(lam, lo, hi, seed):
    d = BoundedExponential(lam, lo, hi)
    rng = np.random.default_rng(seed)
    x = d.sample(rng, 100)
    assert (x >= lo).all() and (x <= hi).all()


@given(st.floats(0.05, 3.0), st.floats(0.01, 2.0), st.integers(0, 2**31 - 1))
@settings(max_examples=25)
def test_truncated_normal_fractional_carry_preserves_rate(mu, sigma, seed):
    """The generator pipeline — truncated-normal count samples emitted
    through the fractional-remainder carry — preserves the long-run rate:
    total integer emissions track both the sampled total (within the one
    carried fraction) and the analytic clamped-normal mean."""
    d = TruncatedNormalCount(mu, sigma)
    rng = np.random.default_rng(seed)
    xs = d.sample(rng, 4000)
    counter = FractionalCounter()
    emitted = sum(counter.emit(x) for x in xs)
    assert abs(emitted - xs.sum()) < 1.0  # only the carry is ever pending
    assert 0.0 <= counter.acc < 1.0
    # long-run emission rate ~ the distribution mean (law of large numbers
    # bound: generous 5 sigma / sqrt(n) envelope keeps flakiness ~zero)
    assert abs(emitted / len(xs) - d.mean) \
        <= 5.0 * max(sigma, 0.05) / np.sqrt(len(xs)) + 1.0 / len(xs)


@given(st.floats(0.001, 5.0), st.floats(0.0, 1.0), st.floats(1.5, 100.0),
       st.sampled_from([1.0, 1e6, GiB]), st.integers(0, 2**31 - 1))
def test_bounded_exponential_clamps_scaled_by_unit(lam, lo, hi, unit, seed):
    """Samples always land in [lo, hi] x unit — the clamp applies before
    the unit scaling (sizes are drawn in GiB and stored in bytes)."""
    d = BoundedExponential(lam, lo, hi, unit=unit)
    rng = np.random.default_rng(seed)
    x = d.sample(rng, 200)
    assert (x >= lo * unit).all() and (x <= hi * unit).all()
    scalar = d.sample(rng)  # n=None: scalar draw obeys the same clamp
    assert lo * unit <= scalar <= hi * unit


@given(st.floats(0.01, 3.0), st.floats(0.01, 2.0), st.integers(0, 2**31 - 1))
@settings(max_examples=25)
def test_truncated_normal_mean_formula(mu, sigma, seed):
    d = TruncatedNormalCount(mu, sigma)
    rng = np.random.default_rng(seed)
    emp = d.sample(rng, 30_000).mean()
    assert abs(emp - d.mean) < 0.05 * max(d.mean, 0.1)


@given(st.lists(st.tuples(st.integers(0, 30), st.floats(1, 100)),
                min_size=1, max_size=100),
       st.floats(50, 500))
def test_sliding_window_never_exceeds_limit(ops, limit):
    w = SlidingWindow(limit)
    allocated = {}
    for key, size in ops:
        if key in allocated:
            w.release(key)
            del allocated[key]
        else:
            if w.allocate(key, size):
                allocated[key] = size
        assert w.used <= limit + 1e-9
        assert abs(w.used - sum(allocated.values())) < 1e-6
    for key in list(allocated):
        w.release(key)
    assert abs(w.used) < 1e-6  # float accumulation drift only


@given(st.floats(1e6, 1e17))
@settings(max_examples=50)
def test_egress_cost_monotone_and_tiered(nbytes):
    cm = GCSCostModel()
    c1 = cm.egress_cost(nbytes)
    c2 = cm.egress_cost(nbytes * 1.5)
    assert c2 >= c1 >= 0
    # effective rate never exceeds the top tier price and never drops
    # below the bottom tier price
    rate = c1 / (nbytes / GiB)
    assert 0.08 - 1e-9 <= rate <= 0.12 + 1e-9


@given(st.integers(2, 64), st.integers(1, 16))
@settings(max_examples=20)
def test_elastic_planner_divisibility(chips, tp_pow):
    from repro.ckpt.failover import ElasticPlanner

    tp = min(tp_pow, chips)
    planner = ElasticPlanner(model_tp=tp)
    plan = planner.plan(chips, global_batch=256)
    assert plan.model == tp
    assert plan.data >= 1
    assert plan.devices <= max(chips, tp)
    assert 256 % max(plan.data * plan.pods, 1) == 0 or plan.data == 1


# ---------------------------------------------------------------------------
# Decision-layer properties (ISSUE 5): interval-overlap frontier membership
# is subset-monotone, and adaptive refinement never drops an evaluated
# point that a dense grid over the same resolved levels would keep on its
# frontier.
# ---------------------------------------------------------------------------

def _decision_points(data):
    from repro.core.scenarios import ScenarioSpec
    from repro.sim.decide import summarize
    from repro.sim.sweep import ScenarioResult

    n_pts = data.draw(st.integers(3, 12))
    n_seeds = data.draw(st.integers(1, 4))
    results = []
    for i in range(n_pts):
        spec = ScenarioSpec(base="III", days=0.1, n_files=100,
                            cache_tb=float(i + 1))
        for s in range(n_seeds):
            jobs = data.draw(st.floats(0, 1000, allow_nan=False))
            cost = data.draw(st.floats(0, 500, allow_nan=False))
            results.append(ScenarioResult(
                spec=spec.__class__(**{**spec.to_dict(), "seed": s}),
                metrics={"jobs_done": jobs}, storage_usd=cost,
                network_usd=0.0, ops_usd=0.0, wall_s=0.0, events=0))
    return summarize(results)


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_ci_frontier_subset_monotone_property(data):
    """For A ⊆ B: ci_frontier(B) ∩ A ⊆ ci_frontier(A). Removing points can
    only remove dominators, never create one — so a refinement that
    evaluates a subset of a dense grid can never discard a point the dense
    grid would keep."""
    from repro.sim.decide import ci_frontier

    points = _decision_points(data)
    mask = [data.draw(st.booleans()) for _ in points]
    subset = [p for p, keep in zip(points, mask) if keep]
    full_front = ci_frontier(points)
    sub_front = ci_frontier(subset)
    for p in full_front:
        if p in subset:
            assert p in sub_front


@given(st.floats(5.0, 40.0), st.floats(10.0, 60.0),
       st.floats(0.0, 10.0), st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_refinement_never_drops_dense_frontier_point(jobs_tau, cost_tau,
                                                     seed_spread, n_seeds):
    """Refinement on a random monotone synthetic cost model: every
    evaluated point that the dense grid over the refinement's resolved
    levels keeps on its frontier is on the refined frontier too."""
    import math as _math

    from repro.core.scenarios import expand_grid, with_seeds
    from repro.sim.decide import ci_frontier, refine_frontier, summarize
    from repro.sim.sweep import ScenarioResult, SweepResult

    def jobs_fn(s):
        c = s.cache_tb if s.cache_tb is not None else 100.0
        return 1000.0 * (1 - _math.exp(-c / jobs_tau)) \
            + seed_spread * (s.seed % 3)

    def cost_fn(s):
        c = s.cache_tb if s.cache_tb is not None else 100.0
        return 15.0 + 150.0 * _math.exp(-c / cost_tau)

    def evaluate(specs):
        return SweepResult(results=[ScenarioResult(
            spec=s, metrics={"jobs_done": jobs_fn(s)},
            storage_usd=cost_fn(s), network_usd=0.0, ops_usd=0.0,
            wall_s=0.0, events=0) for s in specs])

    axes = {"base": "III", "days": 0.1, "n_files": 100,
            "cache_tb": [5.0, 20.0, 40.0, 80.0]}
    res = refine_frontier(axes, evaluate, ("cache_tb",), n_seeds=n_seeds,
                          rel_tol=0.05, max_rounds=4)
    dense_axes = dict(axes)
    dense_axes["cache_tb"] = res.axis_levels["cache_tb"]
    dense = summarize(evaluate(
        with_seeds(expand_grid(dense_axes), n_seeds)).results)
    dense_front = {p.spec for p in ci_frontier(dense)}
    evaluated = {p.spec for p in res.points}
    refined_front = {p.spec for p in res.frontier}
    assert dense_front & evaluated <= refined_front


# ---------------------------------------------------------------------------
# Result-cache key semantics (ISSUE 6): the content address must be a pure
# function of the dynamics identity — invariant under pricing-only changes,
# distinct for any dynamics-affecting change. (Restart stability is covered
# by a subprocess test in tests/test_cache.py.)
# ---------------------------------------------------------------------------

#: Valid value pools per ScenarioSpec field (chosen to satisfy
#: ``__post_init__`` validation, not to be exhaustive).
_SPEC_POOLS = {
    "base": ["I", "II", "III"],
    "days": [0.1, 0.25, 1.0, 2.0],
    "n_files": [100, 1000, 20_000],
    "seed": [0, 1, 2, 7],
    "cache_tb": [None, 5.0, 20.0, 80.0],
    "gcs_limit_tb": [None, 0.0, 50.0],
    "egress": ["internet", "direct", "interconnect"],
    "storage_price": [None, 0.018, 0.026],
    "egress_price": [None, 0.0, 0.05],
    "job_rate_scale": [0.5, 1.0, 2.0],
    "workload": ["steady", "diurnal", "zipf-drift"],
    "curves": [False, True],
}

_DYNAMICS_FIELDS = sorted(set(_SPEC_POOLS) -
                          {"egress", "storage_price", "egress_price"})


@st.composite
def _spec_strategy(draw):
    from repro.core.scenarios import ScenarioSpec

    return ScenarioSpec(**{name: draw(st.sampled_from(pool))
                           for name, pool in _SPEC_POOLS.items()})


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_cache_key_invariant_under_pricing_only_changes(data):
    """Repricing any subset of the PRICING_FIELDS never moves the content
    address: pricing variants share one stored dynamics lane."""
    from dataclasses import replace

    from repro.core.scenarios import PRICING_FIELDS, cache_key

    spec = data.draw(_spec_strategy())
    repriced = replace(spec, **{f: data.draw(st.sampled_from(_SPEC_POOLS[f]))
                                for f in PRICING_FIELDS})
    assert cache_key(repriced) == cache_key(spec)
    assert cache_key(repriced, "jax", 60.0) == cache_key(spec, "jax", 60.0)


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_cache_key_collides_iff_dynamics_identical(data):
    """Two independently drawn specs share a key exactly when their
    dynamics identities coincide — no accidental collisions, no spurious
    misses, for either engine fingerprint."""
    from repro.core.scenarios import cache_key, dynamics_key

    a, b = data.draw(_spec_strategy()), data.draw(_spec_strategy())
    same_dynamics = dynamics_key(a) == dynamics_key(b)
    assert (cache_key(a) == cache_key(b)) == same_dynamics
    assert (cache_key(a, "jax", 60.0) == cache_key(b, "jax", 60.0)) \
        == same_dynamics
    # engines never collide with each other regardless of the spec pair
    assert cache_key(a, "process") != cache_key(b, "jax", 60.0)


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_cache_key_sensitive_to_every_dynamics_field(data):
    """Mutating any single dynamics-affecting field to a different valid
    value always produces a fresh content address."""
    from dataclasses import replace

    from repro.core.scenarios import cache_key

    spec = data.draw(_spec_strategy())
    field = data.draw(st.sampled_from(_DYNAMICS_FIELDS))
    alternatives = [v for v in _SPEC_POOLS[field]
                    if v != getattr(spec, field)]
    mutated = replace(spec, **{field: data.draw(st.sampled_from(alternatives))})
    assert cache_key(mutated) != cache_key(spec), field
    assert cache_key(mutated, "jax", 60.0) != cache_key(spec, "jax", 60.0)


# ---------------------------------------------------- retry backoff (ISSUE 9)
_backoff_policies = st.builds(
    lambda base, mult, cap, jit, seed: __import__(
        "repro.sim.jobs", fromlist=["RetryPolicy"]).RetryPolicy(
            max_attempts=10, base_delay_s=base, multiplier=mult,
            max_delay_s=cap, jitter=jit, seed=seed),
    st.floats(0.0, 10.0, allow_nan=False),
    st.floats(1.0, 8.0, allow_nan=False),
    st.floats(0.0, 100.0, allow_nan=False),
    st.floats(0.0, 2.0, allow_nan=False),
    st.integers(0, 2**31 - 1),
)


@given(_backoff_policies, st.text(min_size=0, max_size=24))
@settings(max_examples=80, deadline=None)
def test_retry_backoff_bounded_monotone_reproducible(policy, job_id):
    """The resilience layer's backoff guarantees, over the whole policy
    space: every delay lands in [0, max_delay_s], each job's delay
    sequence is monotone non-decreasing in the attempt number (the
    jitter term is per job, not per attempt), and the sequence is
    bitwise-reproducible from the policy parameters alone."""
    delays = [policy.delay_s(job_id, a) for a in range(1, 13)]
    assert all(0.0 <= d <= policy.max_delay_s for d in delays)
    assert all(b >= a for a, b in zip(delays, delays[1:]))
    from repro.sim.jobs import RetryPolicy

    clone = RetryPolicy(max_attempts=10, base_delay_s=policy.base_delay_s,
                        multiplier=policy.multiplier,
                        max_delay_s=policy.max_delay_s,
                        jitter=policy.jitter, seed=policy.seed)
    assert [clone.delay_s(job_id, a) for a in range(1, 13)] == delays


@given(st.integers(0, 2**31 - 1), st.floats(0.0, 0.33), st.floats(0.0, 0.33),
       st.floats(0.0, 0.33), st.integers(1, 3),
       st.lists(st.text(min_size=1, max_size=12), min_size=1, max_size=30))
@settings(max_examples=60, deadline=None)
def test_fault_plan_draws_deterministic_and_exclusive(seed, crash, hang,
                                                      transient, attempts,
                                                      job_ids):
    """Fault directives are a pure function of (seed, job, attempt), at
    most one kind fires per attempt, and nothing injects past the
    ``attempts`` gate — the convergence-under-retry property the
    end-to-end bitwise tests rest on."""
    from repro.sim.faults import FaultPlan

    plan = FaultPlan(seed=seed, crash=crash, hang=hang,
                     transient=transient, attempts=attempts)
    for job_id in job_ids:
        for attempt in range(1, attempts + 2):
            d1 = plan.directive(job_id, (), attempt)
            d2 = plan.directive(job_id, (), attempt)
            assert d1 == d2
            if attempt > attempts:
                assert d1 is None
            if d1 is not None:
                assert d1["kind"] in ("crash", "hang", "transient")
