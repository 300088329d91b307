"""Set-up's compile-only warm-up leaves nothing to compile in the window.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The harness compiles, without running, the program of each job-window
bucket that the first request does not use (``run.grid_program``). A
request in that bucket has to find the program compiled: the compiled
program and the one ``run_sweep`` calls are the same, with the same
arguments.
"""

from __future__ import annotations

import dataclasses
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                "src"))

import run  # noqa: E402


def test_compiled_bucket_runs_without_compiling():
    from repro.core.scenarios import pack_specs
    from repro.sim.batched import simulate_packed

    cell = run.load_cell("cfgIII-1M.steady")
    cell["config"]["n_files"] = 20_000
    specs = run.request_specs(cell, run.request_seeds(2 ** 32 + 3, 0, 2))
    grid = pack_specs(specs, tick=float(cell["config"]["tick_s"]))
    other = dataclasses.replace(
        grid, max_jobs_per_tick=4 * grid.max_jobs_per_tick)
    clock = run.CompileClock()
    program, args = run.grid_program(other)
    program.lower(*args).compile()
    assert clock.take()["compiles"] >= 1
    out = simulate_packed(other)
    assert out["jobs_done_site"].shape[0] == grid.n_lanes
    taken = clock.take()
    assert taken["compiles"] == 0 and taken["cache_loads"] == 0, taken
