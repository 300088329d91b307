#!/usr/bin/env python3
"""Chip benchmark: a planner's closed loop of sweep grids on one TPU.

    python3 benchmarks/chip/run.py --workload cfgIII-1M.steady \\
        --seed 12345 --seconds 30 --trace 0

One client sends a grid of scenarios to the batched sweep
(``repro.sim.sweep.run_sweep(specs, backend="jax")``, the entry point a
planner calls), waits for its ``SweepResult``, and sends the next. Each
request takes fresh seeds from a stream made from ``--seed``, so no
request repeats another. Everything about a cell is data: the cell in
``BENCHMARK.json`` names a configuration (``configs/<name>.json``, its
file given in ``BENCHMARK.json``) and a traffic mix
(``traffic/<name>.json``); its limits for ``correct`` are in
``limits/<cell>.json``; each metric, end-to-end or per-layer, is read
from the run's record by ``metrics/<metric>.py``.

Set-up packs the first request, warms the compiled program by running
that request, compiles (without running) the program of every other
job-window bucket the traffic file lists, and then opens the window. The window closes when the last
request that started before ``--seconds`` has finished. Every timing ends
on the host's numpy results, so it has waited for the device. After the
window the plain reference (``reference.py``) replays every lane of every
window request on the host and the comparison decides ``correct``.

With ``--trace 0`` the result line holds the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the JAX profiler and the
repository's tracer, and the line holds the per-layer metrics, the
device's busy time and a ``breakdown``. The last line of standard output
is that JSON object; the numbers compared for ``correct`` end standard
error and the line. There is no CPU path: without a TPU, or with fewer
chips than the cell asks for, the harness exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, Dict, List  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)

import reference  # noqa: E402
import trace_reduce  # noqa: E402


class BenchError(Exception):
    """The benchmark cannot run as asked."""


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> Dict:
    """Resolve a cell of ``BENCHMARK.json`` to its data files by name."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r} "
                         f"(known: {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {
        "name": workload,
        "chips": int(w["chips"]),
        "config": _load_json(os.path.join(root, cfg_entry["file"])),
        "traffic": _load_json(os.path.join(BENCH_DIR, "traffic",
                                           w["traffic"] + ".json")),
        "limits": _load_json(os.path.join(BENCH_DIR, "limits",
                                          workload + ".json")),
        "end_to_end": bench["end_to_end"],
        "per_layer": bench["per_layer"],
    }


def workload_string(workload: Dict) -> str:
    """The program's ``ScenarioSpec.workload`` text for a traffic mix."""
    params = ",".join(f"{k}={v}" for k, v in workload.items() if k != "name")
    return workload["name"] + (":" + params if params else "")


def request_seeds(seed: int, r: int, n: int) -> List[int]:
    """Seeds of request ``r``: ``n`` draws of a stream keyed by
    ``(seed, r)``, so every request of every run differs."""
    rng = np.random.default_rng(np.random.SeedSequence([seed % 2 ** 64, r]))
    return [int(s) for s in rng.integers(0, 2 ** 63, size=n)]


def request_specs(cell: Dict, seeds: List[int]):
    """The request's grid: every seed under every pricing option."""
    from repro.core.scenarios import ScenarioSpec

    cfg, traffic = cell["config"], cell["traffic"]
    return [ScenarioSpec(base=cfg["base"], days=cfg["days"],
                         n_files=cfg["n_files"], seed=s,
                         cache_tb=cfg["disk_limit_tb"],
                         gcs_limit_tb=cfg["gcs_limit_tb"], egress=egress,
                         workload=workload_string(traffic["workload"]))
            for s in seeds for egress in traffic["egress"]]


class CompileClock:
    """Counts XLA backend compiles and their seconds, as JAX reports them.

    JAX reports a program loaded from the persistent compilation cache as
    a backend compile too; ``hits`` counts those loads."""

    def __init__(self):
        from jax import monitoring

        self.n = self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self):
        out = {"compiles": self.n - self.hits, "cache_loads": self.hits,
               "compile_s": self.seconds}
        self.n = self.hits = 0
        self.seconds = 0.0
        return out


def grid_program(grid, cached: bool = True):
    """The jitted program that ``run_sweep(backend="jax")`` runs for
    ``grid`` on one chip, and its arguments in call order (as
    ``batched.simulate_packed`` builds them). ``cached=False`` gives a
    fresh program, outside the program's own cache."""
    from repro.kernels.registry import resolve_tick_impl
    from repro.sim import batched

    make = batched._grid_program if cached else \
        batched._grid_program.__wrapped__
    program = make(len(grid.site_names), grid.max_jobs_per_tick,
                   grid.n_months, resolve_tick_impl("auto").name, None)
    shared = (np.asarray(grid.times), np.asarray(grid.dts),
              np.asarray(grid.month_idx),
              np.arange(grid.n_ticks, dtype=np.int32),
              np.float32(grid.horizon))
    lanes = [np.asarray(getattr(grid, n)) for n in batched._LANE_FIELDS]
    return program, [*shared, *lanes]


def load_reader(name: str) -> Callable:
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool,
             devices) -> Dict:
    """Set up, run the closed-loop window, and return the run's record."""
    import jax

    from repro.core.scenarios import pack_specs
    from repro.obs.trace import get_tracer
    from repro.sim.sweep import run_sweep

    cfg, traffic = cell["config"], cell["traffic"]
    n_seeds = int(traffic["seeds_per_request"])
    tick = float(cfg["tick_s"])
    clock = CompileClock()

    # 1. pack the first request: its shapes are every request's shapes,
    # but for the per-tick job window K, whose buckets the traffic lists
    first = request_specs(cell, request_seeds(seed, 0, n_seeds))
    grid = pack_specs(first, tick=tick)
    shapes = {"lanes": grid.n_lanes, "ticks": grid.n_ticks,
              "files": int(grid.sizes.shape[2]),
              "K": grid.max_jobs_per_tick, "J": int(grid.job_fid.shape[2])}
    say("cell", cell["name"], "shapes", json.dumps(shapes))
    # 2. warm: run that request; compile, without running, the program
    # of every other K bucket (the call then finds it compiled)
    t0 = time.perf_counter()
    warm = run_sweep(first, backend="jax", tick=tick)
    for k in traffic.get("k_buckets", []):
        if k != grid.max_jobs_per_tick:
            program, args = grid_program(
                dataclasses.replace(grid, max_jobs_per_tick=k))
            program.lower(*args).compile()
    del grid
    setup_compiles = clock.take()
    say(f"warm-up: wall={time.perf_counter() - t0!r}s ok={warm.ok} "
        f"{json.dumps(setup_compiles)}")

    tracer = get_tracer()
    profile_dir = None
    if trace:
        tracer.reset()
        tracer.enable()
        profile_dir = tempfile.mkdtemp(prefix="bench_profile_")
        jax.profiler.start_trace(profile_dir)
    # 3. the window
    requests = []
    perf_at_first_ns = None
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    r = 0
    while time.perf_counter() - t_open < seconds:
        r += 1
        specs = request_specs(cell, request_seeds(seed, r, n_seeds))
        with jax.profiler.TraceAnnotation(trace_reduce.REQUEST):
            if perf_at_first_ns is None:
                perf_at_first_ns = time.perf_counter_ns()
            t0 = time.perf_counter()
            res = run_sweep(specs, backend="jax", tick=tick)
            t1 = time.perf_counter()
        ok = bool(res.ok) and len(res.results) == len(specs)
        requests.append({"t0": t0, "t1": t1, "results": res.results,
                         "ok": ok, "lanes": n_seeds,
                         "ticks": shapes["ticks"], "days": cfg["days"]})
    window_s = time.perf_counter() - t_open
    window_compiles = clock.take()
    peak = peak_bytes(devices)
    spans, reduced = [], None
    if trace:
        jax.profiler.stop_trace()
        tracer.disable()
        spans = [{"name": e["name"], "ts": e["ts"], "dur": e["dur"]}
                 for e in tracer.events if e.get("ph") == "X"]
        paths = [os.path.join(d, f) for d, _, fs in os.walk(profile_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if paths:
            reqs, devs = trace_reduce.read_trace(paths[0])
            reduced = trace_reduce.reduce(reqs, devs, spans,
                                          perf_at_first_ns)
        shutil.rmtree(profile_dir, ignore_errors=True)
    say(f"window: {len(requests)} requests in {window_s!r}s, "
        f"{json.dumps(window_compiles)} peak_bytes={peak}")
    return {"setup_s": setup_s, "window_s": window_s, "requests": requests,
            "setup_compiles": setup_compiles,
            "compiles": (window_compiles["compiles"]
                         + window_compiles["cache_loads"]),
            "peak_bytes": peak, "spans": spans, "trace": reduced}


def check(cell: Dict, record: Dict) -> List[list]:
    """Replay every window lane on the reference; ``[name, value, limit]``
    for each number compared (value ``None`` when no lane came back)."""
    cfg, traffic = cell["config"], cell["traffic"]
    pairs = []
    t0 = time.perf_counter()
    for req in record["requests"]:
        if not req["ok"]:
            continue
        by_seed = {}
        for res in req["results"]:
            by_seed.setdefault(res.spec.seed, []).append(res)
        for s, results in by_seed.items():
            ref = reference.simulate(cfg, traffic, s)
            pairs.extend((res, ref) for res in results)
    say(f"reference: {len(pairs)} results replayed in "
        f"{time.perf_counter() - t0!r}s")
    numbers = reference.compare(pairs, cfg)
    return [[name, numbers.get(name), limit]
            for name, limit in cell["limits"]["limits"].items()]


def result_line(cell: Dict, record: Dict, checks: List[list], trace: bool,
                devices) -> Dict:
    reqs = record["requests"]
    failed = sum(not r["ok"] for r in reqs)
    correct = failed == 0 and all(v is not None and v <= lim
                                  for _, v, lim in checks)
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": record["peak_bytes"]}
    line = {"correct": correct, "attempted": len(reqs), "failed": failed,
            "metrics": metrics, "device": device}
    if trace and record["trace"] is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        line["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                             "idle_gaps": record["trace"]["idle_gaps"]}
    # set-up's compiles: a run on a cold compile cache reads compiles > 0
    line["setup"] = record["setup_compiles"]
    line["checks"] = {name: {"value": v, "limit": lim}
                      for name, v, lim in checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError, ValueError, BenchError) as e:
        say(f"cannot load the cell: {e!r}")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax

        from repro.sim.compile_cache import use_compile_cache
    except ImportError as e:
        say(f"cannot import the program: {e}")
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as e:
        say(f"JAX found no devices: {e}")
        return 1
    if devices[0].platform != "tpu":
        say(f"no TPU (JAX platform {devices[0].platform!r}); there is no "
            "CPU path")
        return 1
    if len(devices) < cell["chips"]:
        say(f"the cell needs {cell['chips']} chips, JAX sees "
            f"{len(devices)}")
        return 1
    devices = devices[:cell["chips"]]
    say("compile cache:", use_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    record = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    checks = check(cell, record)
    line = result_line(cell, record, checks, bool(args.trace), devices)
    for name, value, limit in checks:
        say(f"check {name}: {value!r} (limit {limit!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
