"""Batched scenario-sweep engine (paper §5.3: the decision workflow).

The paper's stated purpose for the simulation is "to assist with the
decision process of using commercial cloud storage": compare many scenario
variants — hot-cache sizes, egress pricing/peering options, job arrival
rates, seeds — on a cost vs. throughput frontier. This module turns the
single-run ``HCDCScenario`` into that instrument:

- ``run_scenario(spec)``: one ``ScenarioSpec`` -> ``ScenarioResult``
  (metrics, monthly-bill breakdown, time-series digests, run stats). Specs
  are built via ``repro.core.scenarios`` and executed on the analytic
  ``EventDrivenTransferService`` fast path, so a reduced-scale config runs
  in seconds.
- ``run_sweep(specs)``: executes a batch with process-level parallelism
  (simulations are pure Python and CPU-bound, so threads would serialize on
  the GIL). Results are deterministic per spec — a parallel sweep is
  bit-identical to running each config serially with the same seed.
- ``SweepResult``: ordered results + CSV/JSON export + Pareto-front
  extraction (minimize cloud cost, maximize jobs done) + seed aggregation
  in the paper's Table 6/7/8 mean/sd% presentation.
"""

from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field, replace
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Sequence

from repro.obs.metrics import get_registry, snapshot_and_reset
from repro.obs.trace import get_tracer
from repro.sim.cloud import sum_bills
from repro.sim.output import atomic_write_text, mean_and_error, write_csv

if TYPE_CHECKING:  # repro.core imports repro.sim; keep runtime acyclic
    from repro.core.scenarios import ScenarioSpec


@dataclass
class ScenarioResult:
    """Outcome of one simulated configuration (picklable)."""

    spec: ScenarioSpec
    metrics: Dict[str, float]
    storage_usd: float
    network_usd: float
    ops_usd: float
    wall_s: float
    events: int
    series: Dict[str, Dict[str, float]] = field(default_factory=dict)
    #: Raw per-month billing inputs: ``{"gb_seconds": [...], "egress_bytes":
    #: [...], "class_a": [...], "class_b": [...], "full_months": int}``.
    #: Pricing-independent — feeding them through
    #: ``repro.sim.cloud.bills_from_monthly_totals`` under any cost model
    #: re-bills the run bit-exactly, which is how the persistent result
    #: cache (``repro.sim.cache``) serves pricing variants of one stored
    #: dynamics lane. Empty for synthetic results that never simulated.
    monthly: Dict[str, Any] = field(default_factory=dict)
    #: Counters of the device program that simulated this result (the
    #: batched engine's cloud admission gate: ``gcs_gate_passes``,
    #: ``gcs_refused_ticks``, ``gcs_first_refusal_h``, the last ``None``
    #: when no tick refused). Empty for the event engine and for results
    #: served from the result cache, which ran no device work.
    counters: Dict[str, Any] = field(default_factory=dict)

    @property
    def cost_usd(self) -> float:
        return self.storage_usd + self.network_usd + self.ops_usd

    @property
    def jobs_done(self) -> float:
        return self.metrics["jobs_done"]

    @property
    def jobs_per_day(self) -> float:
        return self.jobs_done / self.spec.days

    def row(self) -> Dict[str, Any]:
        """Flat record for CSV/JSON export."""
        m = self.metrics
        r: Dict[str, Any] = {"label": self.spec.label}
        r.update(self.spec.to_dict())
        del r["curves"]
        r.update(
            jobs_done=m["jobs_done"],
            jobs_per_day=self.jobs_per_day,
            job_waiting_h_mean=m["job_waiting_h_mean"],
            download_pb=m["download_pb"],
            tape_to_disk_pb=sum(v for k, v in m.items()
                                if k.endswith(".tape_to_disk_pb")),
            gcs_to_disk_pb=m["gcs_to_disk_pb"],
            disk_to_gcs_pb=m["disk_to_gcs_pb"],
            gcs_used_pb=m["gcs_used_pb"],
            storage_usd=self.storage_usd,
            network_usd=self.network_usd,
            ops_usd=self.ops_usd,
            cost_usd=self.cost_usd,
            cost_per_kjob=1e3 * self.cost_usd / max(m["jobs_done"], 1.0),
            wall_s=self.wall_s,
            events=self.events,
        )
        return r


def _worker_init() -> None:
    """Initializer for spawned sweep workers.

    Pin JAX (should any import chain pull it in) to CPU before the worker
    touches a task: an accelerator-probing child process can hang on
    device initialization while the parent holds the device — the same
    failure class as the moe multi-device subprocess hang. An inherited
    JAX_PLATFORMS (e.g. the parent exported ``tpu``) is deliberately
    overridden: workers only ever need numpy, so CPU is always right.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    # Fresh baseline for the worker's process-global metrics registry so
    # the per-task snapshot deltas it returns contain only its own work.
    get_registry().reset()


def run_scenario(spec: ScenarioSpec) -> ScenarioResult:
    """Build and run one configuration; the sweep's unit of work.

    Top-level (not a closure) so ``ProcessPoolExecutor`` can pickle it; all
    randomness is derived from ``spec.seed``, so the result is independent
    of which process runs it.
    """
    # Deferred imports: repro.core depends on repro.sim, so importing it at
    # module scope would make ``repro.sim`` circular.
    from repro.core.hcdc import HCDCScenario
    from repro.core.scenarios import build_config

    cfg = build_config(spec)
    t0 = time.perf_counter()
    with get_tracer().span("run_scenario", label=spec.label):
        scenario = HCDCScenario(cfg)
        metrics = scenario.run()
    wall = time.perf_counter() - t0
    reg = get_registry()
    reg.inc("scenario.runs", help="Event-engine scenario executions")
    reg.observe("scenario.wall_s", wall,
                help="Per-scenario event-engine wall time (s)")
    bill = sum_bills(scenario.gcs.bills)
    series = {name: ts.summary() for name, ts in scenario.out.series.items()}
    raw = scenario.gcs.monthly_raw
    monthly = {
        "gb_seconds": [float(r[0]) for r in raw],
        "egress_bytes": [float(r[1]) for r in raw],
        "class_a": [int(r[2]) for r in raw],
        "class_b": [int(r[3]) for r in raw],
        "full_months": int(scenario.gcs.full_months_closed),
    }
    return ScenarioResult(
        spec=spec,
        metrics=metrics,
        storage_usd=bill.storage_usd,
        network_usd=bill.network_usd,
        ops_usd=bill.ops_usd,
        wall_s=wall,
        events=scenario.sim.events_executed,
        series=series,
        monthly=monthly,
    )


def _run_scenario_with_metrics(spec: ScenarioSpec):
    """Pool-worker task: the result plus the worker registry's snapshot
    delta (snapshot-then-reset), so the parent can ``merge`` it and a
    parallel sweep's metrics match a serial run's. Top-level for pickling.
    """
    result = run_scenario(spec)
    return result, snapshot_and_reset()


def pareto_indices(costs: Sequence[float],
                   values: Sequence[float]) -> List[int]:
    """Indices of the non-dominated (min cost, max value) points.

    Returned sorted by cost ascending; of points with identical (cost,
    value) only the first is kept, so the front is a strictly increasing
    cost/value staircase.
    """
    if len(costs) != len(values):
        raise ValueError("costs and values must have equal length")
    order = sorted(range(len(costs)), key=lambda i: (costs[i], -values[i]))
    front: List[int] = []
    best = float("-inf")
    for i in order:
        if values[i] > best:
            front.append(i)
            best = values[i]
    return front


@dataclass
class SweepResult:
    """Ordered results of one sweep (same order as the input specs).

    A sweep that lost work to exhausted retries is *partial*: the failed
    specs are simply absent from ``results`` and described in
    ``failures`` (``repro.sim.jobs.JobFailure`` reports — job id, spec
    labels, failure kind, attempt count, error trail). Callers that
    require completeness check ``ok`` / ``failures`` instead of relying
    on an exception; see ``docs/resilience.md``.
    """

    results: List[ScenarioResult]
    wall_s: float = 0.0
    #: Distinct dynamics lanes actually *simulated* to answer this call
    #: (``None`` when the call ran without get-or-compute accounting). A
    #: fully warm cache read reports 0 here.
    lanes_simulated: Optional[int] = None
    #: Distinct requested specs answered from the persistent result cache.
    cache_hits: int = 0
    #: Structured reports of jobs that exhausted their retry budget
    #: (``repro.sim.jobs.JobFailure``); empty for a complete sweep.
    failures: List[Any] = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.results)

    @property
    def ok(self) -> bool:
        """True when no sweep work was abandoned (the result is
        complete with respect to the requested specs)."""
        return not self.failures

    #: Below this wall-clock floor a throughput rate is noise, not signal.
    WALL_S_FLOOR = 1e-3

    @property
    def configs_per_sec(self) -> Optional[float]:
        """Throughput, or ``None`` when ``wall_s`` is under the 1 ms
        floor — a fully cache-warm (or empty) sweep finishes in
        microseconds, and dividing by that produces a meaningless
        6-digit "rate"."""
        if self.wall_s < self.WALL_S_FLOOR:
            return None
        return len(self.results) / self.wall_s

    # -- frontier ------------------------------------------------------------
    def pareto_front(self) -> List[ScenarioResult]:
        """Cost/throughput frontier: min cloud cost, max jobs done."""
        idx = pareto_indices([r.cost_usd for r in self.results],
                             [r.jobs_done for r in self.results])
        return [self.results[i] for i in idx]

    # -- tabulation ----------------------------------------------------------
    def rows(self) -> List[Dict[str, Any]]:
        front = {id(r) for r in self.pareto_front()}
        out = []
        for r in self.results:
            row = r.row()
            row["pareto"] = int(id(r) in front)
            out.append(row)
        return out

    def aggregate_seeds(self) -> List[Dict[str, Any]]:
        """Group by spec-minus-seed; mean and sd% across seeds (the paper's
        Table 6/7/8 multi-run presentation)."""
        groups: Dict[ScenarioSpec, List[ScenarioResult]] = {}
        for r in self.results:
            groups.setdefault(replace(r.spec, seed=0), []).append(r)
        rows = []
        for key, rs in groups.items():
            jobs_m, jobs_sd, _ = mean_and_error([r.jobs_done for r in rs])
            cost_m, cost_sd, _ = mean_and_error([r.cost_usd for r in rs])
            row: Dict[str, Any] = {"label": key.label.rsplit(",seed=", 1)[0]}
            row.update(key.to_dict())
            del row["curves"], row["seed"]
            row.update(n_seeds=len(rs), jobs_done_mean=jobs_m,
                       jobs_done_sd_pct=jobs_sd, cost_usd_mean=cost_m,
                       cost_usd_sd_pct=cost_sd,
                       cost_per_kjob_mean=1e3 * cost_m / max(jobs_m, 1.0))
            rows.append(row)
        return rows

    # -- export --------------------------------------------------------------
    def to_csv(self, path: str) -> None:
        write_csv(path, self.rows())

    def pareto_to_csv(self, path: str) -> None:
        write_csv(path, [r.row() for r in self.pareto_front()])

    def to_json(self, path: str) -> None:
        """JSON export, committed atomically (tmp file + ``os.replace``)
        like every other export path — a killed run never publishes a
        truncated document."""
        doc = {
            "wall_s": self.wall_s,
            "rows": self.rows(),
            "pareto": [r.spec.label for r in self.pareto_front()],
            "series": {r.spec.label: r.series
                       for r in self.results if r.series},
        }
        if self.configs_per_sec is not None:
            doc["configs_per_sec"] = self.configs_per_sec
        if self.lanes_simulated is not None:
            doc["lanes_simulated"] = self.lanes_simulated
            doc["cache_hits"] = self.cache_hits
        if self.failures:
            doc["failures"] = [f.as_dict() for f in self.failures]
        atomic_write_text(path, json.dumps(doc, indent=2))


def _jobs_engaged(backend: str, retry: Any, faults: Any,
                  transport: Any = None) -> bool:
    """Whether this call routes through the ``repro.sim.jobs`` layer.

    The process backend always does — crash recovery and partial results
    cost it nothing. The jax backend engages only when resilience or
    fleet execution was asked for (``retry``/``faults``/``transport``):
    its plain path runs the whole grid as few large device programs, and
    keeping that path untouched keeps the warm-throughput overhead of
    this feature at zero.
    """
    return (backend == "process" or retry is not None
            or faults is not None or transport is not None)


def _refuse_fleet_on_accelerator(backend: str, transport: Any) -> None:
    """Raise when jax-backend lanes would go to the worker fleet from an
    accelerator host. Fleet workers pin ``JAX_PLATFORMS=cpu``, and a chip
    belongs to one process, so the lanes would run on the host CPU."""
    if backend != "jax" or transport is None:
        return
    from repro.kernels.registry import on_accelerator

    if on_accelerator():
        raise ValueError(
            "backend='jax' with transport= on an accelerator host: fleet "
            "workers run on the CPU, and a chip belongs to one process, so "
            "the lanes would leave the accelerator; use shard=True to "
            "spread lanes over this host's devices")


def _journal_to_cache(cache: Any, backend: str, tick: float,
                      tick_impl: Optional[str]) -> Callable:
    """A per-job completion hook that checkpoints results into the
    persistent cache as they finish (the resume mechanism: a killed run
    re-executed with the same cache recomputes only unfinished jobs).

    Dedups by cache key across calls so pricing variants of one dynamics
    lane still produce a single write, exactly like the bulk
    ``cache.store`` the non-journaled path uses.
    """
    from repro.core.scenarios import cache_key

    seen: set = set()

    def journal(pairs) -> None:
        fresh = []
        for spec, result in pairs:
            if not result.monthly:
                continue
            key = cache_key(spec, backend=backend, tick=tick,
                            tick_impl=tick_impl)
            if key not in seen:
                seen.add(key)
                fresh.append((spec, result))
        if fresh:
            cache.store(fresh, backend=backend, tick=tick,
                        tick_impl=tick_impl)

    return journal


def run_sweep(specs: Sequence[ScenarioSpec], workers: Optional[int] = None,
              progress: Optional[Callable[[int, int, ScenarioResult], None]]
              = None, backend: str = "process",
              tick: float = 10.0, tick_impl: str = "auto",
              lane_chunk: Optional[int] = None,
              devices: Optional[Sequence[Any]] = None,
              cache: Optional[Any] = None,
              record_series=None,
              retry: Optional[Any] = None,
              faults: Optional[Any] = None,
              job_timeout: Optional[float] = None,
              transport: Optional[Any] = None,
              shard: bool = False,
              _journal: Optional[Callable] = None) -> SweepResult:
    """Execute every spec; results keep the input order.

    ``backend`` selects the execution engine:

    - ``"process"`` (default): the event-driven reference engine, one
      Python process per config. Ground truth; bit-deterministic per seed.
    - ``"jax"``: the fixed-tick lane-per-scenario engine
      (``repro.sim.batched``) — the whole grid runs as one ``jit`` +
      ``vmap`` program. Requires uniform ``days``/``n_files`` across the
      grid and matches the reference statistically (Table 2 tolerance),
      not bitwise; ``tick`` sets its clock step in seconds.

    ``tick_impl`` (jax backend only) selects the tick-engine *kernel
    implementation* — ``"jnp"`` | ``"pallas"`` | ``"pallas_interpret"``
    | ``"auto"`` (``repro.kernels.registry``; ``"auto"`` resolves to the
    jnp program on every platform). Not to be confused with ``tick``,
    the clock-step *duration*.

    ``workers``: process count for the process backend; ``None`` uses all
    CPUs (capped at the batch size), ``0``/``1`` runs serially in-process
    (useful under profilers and in tests of determinism).

    ``lane_chunk``/``devices`` (jax backend only): execute the packed
    grid's dynamics lanes in fixed-size chunks — bounded device memory
    and one compile reused across chunks and grids — optionally round-
    robined over several devices. Per-lane results are bitwise identical
    to the unchunked path.

    ``cache``: a ``repro.sim.cache.ResultCache`` (or a cache-directory
    path) turns the call into get-or-compute: specs whose dynamics entry
    is already stored are served from the cache (re-billed for their
    pricing fields, bit-identical to a fresh run on the same engine),
    only the misses are simulated, and their results are stored back.
    ``SweepResult.lanes_simulated``/``cache_hits`` report the split.
    ``tick_impl`` is resolved to its concrete implementation *before*
    keying, so entries from different kernel implementations never
    cross-serve (``"jnp"`` keeps the legacy key: it is bitwise the
    pre-registry engine).

    ``record_series`` (jax backend only): per-tick series capture —
    ``True`` samples every tick, an int is the sample stride in ticks;
    each result then carries the event-engine-schema summary digests in
    ``.series`` (see ``repro.sim.batched.series_from_capture``). The
    process backend records series via ``spec.curves`` instead.

    ``retry``/``faults``/``job_timeout`` (see ``docs/resilience.md``):
    fault-tolerant execution through ``repro.sim.jobs``. ``retry`` is a
    ``jobs.RetryPolicy`` (bounded deterministic exponential backoff);
    ``faults`` a ``faults.FaultPlan`` (or spec string / dict) injecting
    seeded crashes / hangs / transient errors / corrupt cache reads;
    ``job_timeout`` a per-attempt wall-clock deadline in seconds. The
    process backend always runs through the job layer (a worker crash
    costs retries, not the sweep); the jax backend shards its packed
    grid into lane-chunk jobs when ``retry`` or ``faults`` is given.
    Work that exhausts its retry budget is *dropped, not fatal*: the
    returned ``SweepResult`` is partial, with the losses described in
    ``SweepResult.failures``. With ``cache`` set, completions are
    journaled per job, so re-running a killed sweep against the same
    cache recomputes only the unfinished jobs (checkpointed resume).

    ``transport`` (see ``docs/distributed.md``): run the jobs on a
    persistent worker fleet (``repro.sim.runners``) instead of the
    serial executor / anonymous pool — ``"subprocess"`` spawns local
    worker processes, ``"local"`` executes inline (tests), a callable is
    a custom ``Transport`` factory (the remote-host seam). Works with
    both backends (the jax backend fans its lane-chunk jobs across the
    fleet) and composes with ``retry``/``faults``/``job_timeout``. Fleet
    workers run JAX on the CPU, so on an accelerator host the jax
    backend refuses ``transport`` (``ValueError``); use ``shard``.

    ``shard`` (jax backend only): run each lane batch as one
    ``jax.shard_map`` program over the local device mesh
    (``repro.parallel.sharding.lane_mesh``) instead of the per-chunk
    Python loop. Per-lane results stay bitwise identical (lane programs
    exchange no collectives). Mutually exclusive with ``devices``.
    """
    if backend != "jax" and tick_impl != "auto":
        raise ValueError("tick_impl applies to backend='jax' only")
    if backend != "jax" and record_series not in (None, False):
        raise ValueError("record_series applies to backend='jax' only "
                         "(the process backend records curves via "
                         "spec.curves)")
    if shard and backend != "jax":
        raise ValueError("shard applies to backend='jax' only")
    _refuse_fleet_on_accelerator(backend, transport)
    from repro.sim.faults import as_faults

    faults = as_faults(faults)
    impl_name: Optional[str] = None
    if backend == "jax":
        from repro.kernels.registry import resolve_tick_impl

        impl_name = resolve_tick_impl(tick_impl).name
    if cache is not None:
        from repro.core.scenarios import dynamics_key
        from repro.sim.cache import ResultCache, as_cache  # imports us

        cache = as_cache(cache)
        if faults is not None and faults.corrupt > 0.0:
            # Corrupt-read injection wraps a *local* view of the caller's
            # backend (the caller's ResultCache object is not mutated);
            # the cache detects the garbage, drops the entry, recomputes.
            from repro.sim.faults import FaultyBackend

            cache = ResultCache(FaultyBackend(cache.backend, faults))
        specs = list(specs)
        t0 = time.perf_counter()
        engaged = _jobs_engaged(backend, retry, faults, transport)
        hits = cache.fetch(specs, backend=backend, tick=tick,
                           tick_impl=impl_name)
        miss = [s for s in dict.fromkeys(specs) if s not in hits]
        computed: Dict["ScenarioSpec", ScenarioResult] = {}
        failures: List[Any] = []
        if miss:
            journal = (_journal_to_cache(cache, backend, tick, impl_name)
                       if engaged else None)
            res = run_sweep(miss, workers=workers, progress=progress,
                            backend=backend, tick=tick,
                            tick_impl=impl_name or "auto",
                            lane_chunk=lane_chunk, devices=devices,
                            record_series=record_series,
                            retry=retry, faults=faults,
                            job_timeout=job_timeout, transport=transport,
                            shard=shard, _journal=journal)
            # Key by result spec, not input order: a partial result has
            # fewer entries than ``miss`` and zip would misalign them.
            computed = {r.spec: r for r in res.results}
            failures = list(res.failures)
            if not engaged:
                # The plain jax path has no per-job journal; store in bulk.
                cache.store(computed.items(), backend=backend, tick=tick,
                            tick_impl=impl_name)
        merged = {**hits, **computed}
        return SweepResult(
            results=[merged[s] for s in specs if s in merged],
            wall_s=time.perf_counter() - t0,
            lanes_simulated=len({dynamics_key(s) for s in computed}),
            cache_hits=len(hits),
            failures=failures)
    if backend == "jax":
        from repro.sim.batched import run_sweep_jax  # deferred: needs jax

        return run_sweep_jax(specs, tick=tick, progress=progress,
                             tick_impl=impl_name,
                             lane_chunk=lane_chunk, devices=devices,
                             record_series=record_series,
                             retry=retry, faults=faults,
                             job_timeout=job_timeout, workers=workers,
                             transport=transport, shard=shard,
                             journal=_journal)
    if lane_chunk is not None or devices is not None:
        raise ValueError("lane_chunk/devices apply to backend='jax' only")
    if backend != "process":
        raise ValueError(f"unknown backend {backend!r} "
                         "(expected 'process' or 'jax')")
    from repro.sim import jobs as joblib

    specs = list(specs)
    if workers is None:
        workers = min(len(specs), os.cpu_count() or 1)
    t0 = time.perf_counter()
    # One job per distinct spec (duplicates in the request are answered
    # from the same result), executed through the registry so a worker
    # failure costs retries — never the completed portion of the sweep.
    unique = list(dict.fromkeys(specs))
    policy = retry if retry is not None else joblib.RetryPolicy()
    jobs_list = [joblib.Job(job_id=f"spec{i:04d}", payload=s,
                            labels=(s.label,), timeout_s=job_timeout)
                 for i, s in enumerate(unique)]
    on_done = None
    if _journal is not None:
        def on_done(job, result):
            _journal([(job.payload, result)])
    if transport is not None:
        from repro.sim.runners import run_fleet_jobs

        _res, registry = run_fleet_jobs(
            jobs_list, workers=max(1, min(workers, len(unique))),
            transport=transport, ctx={"kind": "scenario"},
            policy=policy, faults=faults,
            progress=progress, on_done=on_done)
    elif workers <= 1 or len(unique) <= 1:
        def run_one(job):
            return run_scenario(job.payload)

        _res, registry = joblib.run_local_jobs(
            jobs_list, run_one, policy=policy, faults=faults,
            progress=progress, on_done=on_done)
    else:
        # Spawned (not forked) pool: callers may have JAX loaded, whose
        # thread pools make forked children deadlock-prone; the sweep
        # worker itself only needs numpy, so spawn startup stays cheap.
        _res, registry = joblib.run_process_jobs(
            jobs_list, workers=workers, policy=policy, faults=faults,
            progress=progress, on_done=on_done)
    by_spec = {job.payload: job.result for job in registry.jobs.values()
               if job.state == joblib.DONE}
    return SweepResult(
        results=[by_spec[s] for s in specs if s in by_spec],
        wall_s=time.perf_counter() - t0,
        failures=registry.failures())


class SweepDriver:
    """Iterative ``run_sweep`` front-end with cross-round memoization.

    The decision-support layer (``repro.sim.decide``) calls the sweep *in a
    loop* — adaptive grid refinement, break-even bisection — where
    successive rounds re-request many already-simulated specs plus a few
    new ones. The driver executes only the unseen specs (one ``run_sweep``
    call per round, so new specs still batch into one packed grid on the
    jax backend, whose K/J shape bucketing keeps the compiled program
    cached across rounds) and answers the rest from memory.

    It also keeps the books the decision layer reports on:

    - ``lanes_simulated``: distinct dynamics lanes ever *simulated* (the
      ``repro.core.scenarios.dynamics_key`` identity — the
      backend-independent lane-efficiency denominator). Note the memo is
      per exact spec: pricing-only variants of a memoized spec arriving
      in a *later* call still re-simulate their lane (``pack_specs``
      dedups within one packed grid only) unless a persistent cache
      serves them, which is why the decide solvers batch each round's
      pricing probes into one call;
    - ``configs_run`` / ``sweep_calls`` / ``wall_s``: raw work counters —
      cache-served specs never count as work;
    - ``cache_hits``: specs answered from the persistent result cache.

    ``cache`` (a ``repro.sim.cache.ResultCache`` or a cache-directory
    path) adds a persistent lookup tier between the in-memory memo and
    the engines: memo -> cache -> simulate. Simulated results are stored
    back, so a re-run of the same workflow — same process or next week's
    CI job — answers entirely from disk (``lanes_simulated`` stays 0).
    """

    def __init__(self, backend: str = "jax", tick: float = 10.0,
                 workers: Optional[int] = None,
                 tick_impl: str = "auto",
                 lane_chunk: Optional[int] = None,
                 devices: Optional[Sequence[Any]] = None,
                 progress: Optional[Callable[[int, int, ScenarioResult],
                                             None]] = None,
                 cache: Optional[Any] = None,
                 record_series=None,
                 retry: Optional[Any] = None,
                 faults: Optional[Any] = None,
                 job_timeout: Optional[float] = None,
                 transport: Optional[Any] = None,
                 shard: bool = False):
        if backend != "jax" and tick_impl != "auto":
            raise ValueError("tick_impl applies to backend='jax' only")
        if backend != "jax" and record_series not in (None, False):
            raise ValueError("record_series applies to backend='jax' only")
        if shard and backend != "jax":
            raise ValueError("shard applies to backend='jax' only")
        _refuse_fleet_on_accelerator(backend, transport)
        from repro.sim.faults import as_faults

        self.backend = backend
        self.tick = tick
        self.tick_impl = tick_impl
        self.record_series = record_series
        #: resolved lazily on first run (importing jax to resolve
        #: ``"auto"`` is deferred until the jax backend actually runs)
        self._impl_name: Optional[str] = None
        self.workers = workers
        self.lane_chunk = lane_chunk
        self.devices = devices
        self.progress = progress
        self.retry = retry
        self.faults = as_faults(faults)
        self.job_timeout = job_timeout
        self.transport = transport
        self.shard = shard
        if cache is not None:
            from repro.sim.cache import as_cache  # deferred: imports us

            cache = as_cache(cache)
        self.cache = cache
        self._memo: Dict["ScenarioSpec", ScenarioResult] = {}
        self._lane_keys: set = set()
        self.sweep_calls = 0
        self.configs_run = 0
        self.cache_hits = 0
        self.wall_s = 0.0
        #: cumulative ``JobFailure`` reports across every round; the
        #: decision layer reads this to degrade its claims
        self.failures: List[Any] = []

    @property
    def lanes_simulated(self) -> int:
        return len(self._lane_keys)

    def __call__(self, specs: Sequence["ScenarioSpec"]) -> SweepResult:
        return self.run(specs)

    def _resolved_impl(self) -> Optional[str]:
        """The concrete ``tick_impl`` name for cache keying (jax backend
        only; resolving ``"auto"`` imports jax, so it happens on first
        use and is then pinned for the driver's lifetime)."""
        if self.backend != "jax":
            return None
        if self._impl_name is None:
            from repro.kernels.registry import resolve_tick_impl

            self._impl_name = resolve_tick_impl(self.tick_impl).name
        return self._impl_name

    def run(self, specs: Sequence["ScenarioSpec"]) -> SweepResult:
        """Results for ``specs`` in order, simulating only the unseen ones."""
        from repro.core.scenarios import dynamics_key

        specs = list(specs)
        new = [s for s in dict.fromkeys(specs) if s not in self._memo]
        t0 = time.perf_counter()
        hits = 0
        if new and self.cache is not None:
            served = self.cache.fetch(new, backend=self.backend,
                                      tick=self.tick,
                                      tick_impl=self._resolved_impl())
            self._memo.update(served)
            hits = len(served)
            self.cache_hits += hits
            new = [s for s in new if s not in served]
        lanes_before = len(self._lane_keys)
        round_failures: List[Any] = []
        if new:
            engaged = _jobs_engaged(self.backend, self.retry, self.faults,
                                    self.transport)
            journal = None
            if self.cache is not None and engaged:
                journal = _journal_to_cache(self.cache, self.backend,
                                            self.tick,
                                            self._resolved_impl())
            res = run_sweep(new, workers=self.workers,
                            progress=self.progress, backend=self.backend,
                            tick=self.tick,
                            tick_impl=self._resolved_impl() or "auto",
                            lane_chunk=self.lane_chunk,
                            devices=self.devices,
                            record_series=self.record_series,
                            retry=self.retry, faults=self.faults,
                            job_timeout=self.job_timeout,
                            transport=self.transport, shard=self.shard,
                            _journal=journal)
            self.sweep_calls += 1
            self.configs_run += len(res.results)
            self.wall_s += res.wall_s
            # Key by result spec, not request order: a partial result
            # has fewer entries than ``new`` and zip would misalign.
            for result in res.results:
                self._memo[result.spec] = result
                self._lane_keys.add(dynamics_key(result.spec))
            round_failures = list(res.failures)
            self.failures.extend(round_failures)
            if self.cache is not None and not engaged:
                self.cache.store(((r.spec, r) for r in res.results),
                                 backend=self.backend, tick=self.tick,
                                 tick_impl=self._resolved_impl())
        reg = get_registry()
        reg.set_gauge("lanes.simulated", self.lanes_simulated,
                      help="Distinct dynamics lanes simulated by the "
                           "driver (0 = fully cache-warm)")
        reg.set_gauge("configs.run", self.configs_run,
                      help="Specs actually executed by the driver")
        reg.set_gauge("sweep.calls", self.sweep_calls,
                      help="run_sweep invocations issued by the driver")
        reg.set_gauge("sweep.wall_s", self.wall_s,
                      help="Cumulative driver simulation wall time (s)")
        return SweepResult(results=[self._memo[s] for s in specs
                                    if s in self._memo],
                           wall_s=time.perf_counter() - t0,
                           lanes_simulated=len(self._lane_keys) - lanes_before,
                           cache_hits=hits,
                           failures=round_failures)
