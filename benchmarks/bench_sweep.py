"""Benchmark: scenario-sweep engine throughput (configs/sec, lanes/sec).

Part 1 times the event-driven reference engine on the same reduced-scale
grid twice — serially in-process and through the process pool — so the
derived column shows absolute configs/sec and the parallel speedup.

Part 2 times the batched lane-per-scenario JAX backend
(``run_sweep(..., backend="jax")``) on a pricing-heavy §5.3 decision grid
(cache sizes x egress options x storage prices x seeds). Pricing axes are
billing-only, so the packed grid simulates one dynamics lane per
(cache, seed) point and bills every pricing variant from it. The jax rows
report both configs/sec (completed configurations, including the pricing
fan-out) and raw simulated lanes/sec; ``sweep.jax_speedup`` compares
batched configs/sec (warm, after the one-off XLA compile reported
separately as ``cold``) against the process pool measured on an
evenly-sampled subset of the *same* grid.

Part 3 is the lane-scaling panel (``sweep.jax.lane_scaling.<N>lane``):
simulated lanes/sec at 16/64/256-lane grids, executed through the
bounded-memory ``lane_chunk`` path so every grid size reuses one compiled
chunk program. CI diffs the warm/lanes-per-sec rows against the committed
``BENCH_4.json`` baseline (``scripts/check_bench_regression.py``).

Part 4 is the workload-sensitivity panel: one batched grid sweeping the
``repro.sim.workload`` access-pattern axis on a fixed cache point. Each
``sweep.workload.<model>`` row's derived column is that model's jobs-done
relative to the stationary baseline — how much the access-stream *shape*
(day/night cycles, reprocessing bursts, popularity drift) moves the
paper's throughput observable at unchanged mean pricing knobs.

Part 5 drives the decision layer (``repro.sim.decide``) end-to-end on the
pricing grid: adaptive frontier refinement plus the displaced-disk and
break-even solves. ``sweep.decide.lane_fraction`` tracks refinement lane
efficiency vs an equivalent-resolution dense grid and
``sweep.decide.displaced_tb`` the headline displaced-capacity figure.

Part 6 is the persistent result cache (``repro.sim.cache``, ISSUE 6):
the pricing grid swept cold (empty cache directory, every lane simulated
and stored) and then warm through a *fresh* ``SweepDriver`` (every config
served from disk, zero lanes simulated). ``sweep.cache.warm``'s derived
column is the cold/warm wall-time ratio — the acceptance bar is >= 5x.

Part 7 is the telemetry-overhead row (``sweep.obs.overhead``, ISSUE 8):
the same warm pricing grid with the metrics registry enabled vs disabled,
interleaved min-of-3. Its derived column is the enabled/disabled wall
ratio; the acceptance bar is < 1.05 (< 5% of warm throughput).

Part 8 is the resilience-overhead row (``sweep.resilience.overhead``,
the fault-tolerant jobs layer of ``repro.sim.jobs``): the warm pricing
grid executed through the job registry (retries enabled, no faults
injected) vs the plain path, both at the same ``lane_chunk`` so only the
registry bookkeeping differs. Its derived column is the jobs/plain wall
ratio; the acceptance bar is < 1.05 (docs/resilience.md).

Spawned pool workers are pinned to ``JAX_PLATFORMS=cpu`` by
``run_sweep``'s worker initializer, so the process rows cannot hang
probing accelerator devices while this process holds them.
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Dict, List, Optional

from repro.core.scenarios import (ScenarioSpec, dynamics_key, expand_grid,
                                  with_seeds)
from repro.sim.sweep import SweepDriver, run_sweep

#: Clock step (seconds) for the batched-backend throughput rows. Coarser
#: than the 10 s generator interval: the per-tick fixed cost dominates
#: batched wall time on CPU, and
#: ``test_batched.test_jax_backend_tick_coarsening_stays_close`` pins this
#: exact tick within 2%/5% (jobs/cost) of the 10 s clock.
JAX_BENCH_TICK = 60.0


def _cps(res) -> float:
    """``configs_per_sec`` as a number: the floor makes it ``None`` on
    sub-millisecond walls, which a derived column reports as 0."""
    return res.configs_per_sec or 0.0


def _grid(n_configs: int, days: float, n_files: int):
    cache = [20.0, 50.0, 100.0]
    egress = ["internet", "direct", "interconnect"]
    specs = expand_grid({"base": "III", "days": days, "n_files": n_files,
                         "cache_tb": cache, "egress": egress})
    seeds = max(1, -(-n_configs // len(specs)))  # ceil
    return with_seeds(specs, seeds)[:n_configs]


def _pricing_grid(days: float, n_files: int, n_prices: int, n_seeds: int):
    """§5.3 decision grid: 4 cache points x 3 egress x N storage prices
    x seeds. Dynamics lanes = 4 x seeds; the rest is billing fan-out."""
    prices = [round(0.018 + 0.002 * i, 3) for i in range(n_prices)]
    specs = expand_grid({"base": "III", "days": days, "n_files": n_files,
                         "cache_tb": [10.0, 20.0, 40.0, 80.0],
                         "egress": ["internet", "direct", "interconnect"],
                         "storage_price": prices})
    return with_seeds(specs, n_seeds)


#: Workload-sensitivity panel: the stationary baseline plus one
#: representative of each non-stationary family (docs/workloads.md).
#: Periods are short so the bench's sub-day horizon covers whole waves
#: (a 24 h period would pin the horizon inside the first peak phase).
WORKLOAD_PANEL = (
    "steady",
    "diurnal:amplitude=0.8,period_h=1.2",
    "campaign:period_h=1.2,duty=0.25,peak=3,off=0.5",
    "zipf-drift:power_end=1.5",
)


#: Fixed chunk size for the lane-scaling rows: every grid size reuses the
#: same compiled chunk program, so the scaling panel pays one XLA compile
#: and the rows measure pure execution throughput.
LANE_SCALING_CHUNK = 16


def _lane_scaling_rows(days: float, n_files: int,
                       lane_counts: List[int]) -> List[Dict]:
    """``sweep.jax.lane_scaling.<N>lane``: simulated dynamics lanes/sec at
    growing grid sizes, executed through the bounded-memory lane-chunked
    path (ISSUE 4). Each lane is a distinct seed, so nothing dedupes."""
    rows = []
    for n in lane_counts:
        specs = with_seeds([ScenarioSpec(base="III", days=days,
                                         n_files=n_files, cache_tb=20.0)], n)
        # Absorb the compile with the full grid itself: a sliced warm-up
        # can bucket K/J to a smaller power of two and leave an XLA
        # recompile inside the timed run. After the first grid size, the
        # shapes usually hit the cache and this run is nearly free.
        run_sweep(specs, backend="jax", tick=JAX_BENCH_TICK,
                  lane_chunk=LANE_SCALING_CHUNK)
        warm = run_sweep(specs, backend="jax", tick=JAX_BENCH_TICK,
                         lane_chunk=LANE_SCALING_CHUNK)
        rows.append({"name": f"sweep.jax.lane_scaling.{n}lane",
                     "us_per_call": warm.wall_s / n * 1e6,
                     "derived": n / warm.wall_s if warm.wall_s > 0 else 0.0})
    return rows


def _decide_rows(days: float, n_files: int, n_prices: int,
                 fast: bool) -> List[Dict]:
    """``sweep.decide.*``: the decision workflow driven end-to-end on the
    bench pricing grid (ISSUE 5). ``lane_fraction`` is the adaptive
    refinement's simulated-lane count relative to an equivalent-resolution
    dense grid (lower is better; the acceptance bar is <= 0.5, asserted in
    ``tests/test_decide.py``); ``displaced_tb`` is the headline quantity —
    on-prem disk displaced by the recommended cloud cache."""
    from repro.sim.decide import decide

    prices = [round(0.018 + 0.002 * i, 3) for i in range(n_prices)]
    axes = {"base": "III", "days": days, "n_files": n_files,
            "cache_tb": [10.0, 20.0, 40.0, 80.0],
            "egress": ["internet", "direct", "interconnect"],
            "storage_price": prices}
    g = 4 * 3 * n_prices * 2  # configs incl. pricing fan-out, 2 seeds
    driver = SweepDriver(backend="jax", tick=JAX_BENCH_TICK)
    t0 = time.perf_counter()
    report = decide(axes, driver, n_seeds=2,
                    max_rounds=2 if fast else 3)
    wall = time.perf_counter() - t0
    ref = report.refine
    return [
        {"name": f"sweep.decide.workflow.{g}cfg",
         "us_per_call": wall / g * 1e6,
         "derived": driver.configs_run / wall if wall > 0 else 0.0},
        {"name": f"sweep.decide.lane_fraction.{ref.lanes_used}of"
                 f"{ref.dense_lanes}",
         "us_per_call": wall * 1e6,
         "derived": ref.lane_fraction},
        {"name": "sweep.decide.displaced_tb",
         "us_per_call": wall * 1e6,
         "derived": report.displaced.displaced_tb
         if report.displaced.min_cache_tb is not None else 0.0},
    ]


def _cache_rows(days: float, n_files: int, n_prices: int) -> List[Dict]:
    """``sweep.cache.{cold,warm}``: the pricing grid through a
    tempdir-backed persistent result cache (ISSUE 6). Cold simulates and
    stores every dynamics lane; warm drives a *fresh* ``SweepDriver``
    (empty memo — only the on-disk store answers) and must simulate zero
    lanes. ``sweep.cache.warm``'s derived column is the cold/warm speedup
    (acceptance: >= 5x)."""
    import shutil
    import tempfile

    specs = _pricing_grid(days, n_files, n_prices=n_prices, n_seeds=2)
    tmp = tempfile.mkdtemp(prefix="bench_sweep_cache_")
    try:
        cold_drv = SweepDriver(backend="jax", tick=JAX_BENCH_TICK, cache=tmp)
        t0 = time.perf_counter()
        cold_drv.run(specs)
        cold_wall = time.perf_counter() - t0
        warm_drv = SweepDriver(backend="jax", tick=JAX_BENCH_TICK, cache=tmp)
        t0 = time.perf_counter()
        warm = warm_drv.run(specs)
        warm_wall = time.perf_counter() - t0
        if warm.lanes_simulated:
            raise RuntimeError(
                f"warm cache re-run simulated {warm.lanes_simulated} lanes "
                "(expected 0) — the result cache is not serving")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    g = len(specs)
    return [
        {"name": f"sweep.cache.cold.{g}cfg",
         "us_per_call": cold_wall / g * 1e6,
         "derived": g / cold_wall if cold_wall > 0 else 0.0},
        {"name": f"sweep.cache.warm.{g}cfg",
         "us_per_call": warm_wall / g * 1e6,
         "derived": cold_wall / warm_wall if warm_wall > 0 else 0.0},
    ]


def _obs_overhead_rows(jspecs: List[ScenarioSpec]) -> List[Dict]:
    """``sweep.obs.overhead``: warm batched sweeps with the telemetry
    registry enabled vs disabled (ISSUE 8), interleaved min-of-3 so OS
    noise cancels. The derived column is enabled/disabled wall — the
    acceptance bar is < 1.05 (telemetry costs < 5% of warm throughput).
    The compile is already absorbed by the caller's warm run."""
    from repro.obs.metrics import get_registry

    reg = get_registry()
    on = off = float("inf")
    try:
        for _ in range(3):
            reg.disable()
            t0 = time.perf_counter()
            run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK)
            off = min(off, time.perf_counter() - t0)
            reg.enable()
            t0 = time.perf_counter()
            run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK)
            on = min(on, time.perf_counter() - t0)
    finally:
        reg.enable()
    return [{"name": f"sweep.obs.overhead.{len(jspecs)}cfg",
             "us_per_call": on / len(jspecs) * 1e6,
             "derived": on / off if off > 0 else 0.0}]


def _resilience_overhead_rows(jspecs: List[ScenarioSpec]) -> List[Dict]:
    """``sweep.resilience.overhead``: warm batched sweeps through the
    fault-tolerant jobs layer (registry + per-chunk journaling hooks,
    retries enabled, zero faults injected) vs the plain path, interleaved
    min-of-3 so OS noise cancels. Both sides use the same ``lane_chunk``
    so the chunked program is identical and only the job-registry
    bookkeeping differs. The derived column is jobs/plain wall — the
    acceptance bar is < 1.05 (resilience costs < 5% of warm throughput
    when nothing fails, docs/resilience.md). Display-only: tracked in
    the nightly summary, not the bench-smoke regression gate."""
    from repro.sim.jobs import RetryPolicy

    chunk = 2
    run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK,
              lane_chunk=chunk)  # absorb the chunked-program compile
    plain = jobs = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK,
                  lane_chunk=chunk)
        plain = min(plain, time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK,
                  lane_chunk=chunk, retry=RetryPolicy())
        jobs = min(jobs, time.perf_counter() - t0)
    return [{"name": f"sweep.resilience.overhead.{len(jspecs)}cfg",
             "us_per_call": jobs / len(jspecs) * 1e6,
             "derived": jobs / plain if plain > 0 else 0.0}]


def _workload_rows(days: float, n_files: int) -> List[Dict]:
    specs = expand_grid({"base": "III", "days": days, "n_files": n_files,
                         "cache_tb": 20.0, "workload": list(WORKLOAD_PANEL)})
    res = run_sweep(specs, backend="jax", tick=JAX_BENCH_TICK)
    by = {r.spec.workload: r for r in res.results}
    steady_jobs = max(by["steady"].jobs_done, 1.0)
    rows = [
        {"name": f"sweep.workload.{wl.partition(':')[0]}",
         "us_per_call": res.wall_s / len(specs) * 1e6,
         "derived": by[wl].jobs_done / steady_jobs}
        for wl in WORKLOAD_PANEL
    ]
    rows.append({"name": f"sweep.workload.batch.{len(specs)}cfg",
                 "us_per_call": res.wall_s * 1e6,
                 "derived": _cps(res)})
    return rows


def run(n_configs: int = 8, days: float = 0.25, n_files: int = 4000,
        workers: Optional[int] = None, fast: bool = False) -> List[Dict]:
    specs = _grid(n_configs, days, n_files)
    workers = workers or min(len(specs), os.cpu_count() or 1)
    serial = run_sweep(specs, workers=1)
    par = run_sweep(specs, workers=workers)
    events = sum(r.events for r in serial.results)
    rows = [
        {"name": f"sweep.serial.{len(specs)}cfg",
         "us_per_call": serial.wall_s / len(specs) * 1e6,
         "derived": _cps(serial)},
        {"name": f"sweep.parallel{workers}.{len(specs)}cfg",
         "us_per_call": par.wall_s / len(specs) * 1e6,
         "derived": _cps(par)},
        {"name": "sweep.speedup",
         "us_per_call": par.wall_s * 1e6,
         "derived": serial.wall_s / par.wall_s if par.wall_s > 0 else 0.0},
        {"name": "sweep.events_per_sec_serial",
         "us_per_call": serial.wall_s * 1e6,
         "derived": events / serial.wall_s if serial.wall_s > 0 else 0.0},
    ]

    # -- batched (jax) backend vs the process pool on one decision grid --
    jdays, jfiles = (0.1, 1000) if fast else (0.25, 1000)
    jspecs = _pricing_grid(jdays, jfiles,
                           n_prices=3 if fast else 9, n_seeds=2)
    n_sub = 8 if fast else 24
    stride = max(1, len(jspecs) // n_sub)
    subset = jspecs[::stride][:n_sub]
    # dynamics-lane count for the row label (the pack-time dedup rule:
    # pricing-only fields do not change the simulated dynamics)
    n_lanes = len({dynamics_key(s) for s in jspecs})
    cold = run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK)
    warm = run_sweep(jspecs, backend="jax", tick=JAX_BENCH_TICK)
    base = run_sweep(subset, workers=workers)
    warm_cps = _cps(warm)  # configs/sec (lanes x pricing fan-out)
    base_cps = _cps(base)
    g = len(jspecs)
    rows += [
        {"name": f"sweep.jax.cold.{g}cfg{n_lanes}lane",
         "us_per_call": cold.wall_s / g * 1e6,
         "derived": _cps(cold)},
        {"name": f"sweep.jax.warm.{g}cfg{n_lanes}lane",
         "us_per_call": warm.wall_s / g * 1e6,
         "derived": warm_cps},
        {"name": f"sweep.jax.lanes_per_sec.{n_lanes}lane",
         "us_per_call": warm.wall_s / n_lanes * 1e6,
         "derived": n_lanes / warm.wall_s if warm.wall_s > 0 else 0.0},
        {"name": f"sweep.jax.process_baseline.{len(subset)}cfg",
         "us_per_call": base.wall_s / len(subset) * 1e6,
         "derived": base_cps},
        {"name": "sweep.jax_speedup",
         "us_per_call": warm.wall_s * 1e6,
         "derived": warm_cps / base_cps if base_cps > 0 else 0.0},
    ]

    rows += _obs_overhead_rows(jspecs)
    rows += _resilience_overhead_rows(jspecs)
    rows += _lane_scaling_rows(0.1, jfiles,
                               [16, 64] if fast else [16, 64, 256])
    rows += _workload_rows(jdays, jfiles)
    rows += _decide_rows(jdays, jfiles, n_prices=3 if fast else 9,
                         fast=fast)
    rows += _cache_rows(jdays, jfiles, n_prices=3 if fast else 9)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", type=int, default=8)
    ap.add_argument("--days", type=float, default=0.25)
    ap.add_argument("--files", type=int, default=4000)
    ap.add_argument("--workers", type=int, default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    for r in run(args.configs, args.days, args.files, args.workers,
                 fast=args.fast):
        print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']:.4g}")


if __name__ == "__main__":
    main()
