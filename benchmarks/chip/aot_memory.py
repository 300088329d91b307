#!/usr/bin/env python3
"""Compile each cell's grid program for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_memory.py

Packs each cell's first request (its shapes are every request's shapes),
compiles the program the window runs for one chip of a described
``v5e:2x2`` topology, and prints one JSON line per cell with the
compiler's ``memory_analysis()``. A compile is not a chip run: it says
nothing about times, and the chip's ``peak_bytes_in_use`` is measured by
``run.py``.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

from run import (ROOT, grid_program, load_cell, request_seeds,  # noqa: E402
                 request_specs)

sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core.scenarios import pack_specs

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        specs = request_specs(cell, request_seeds(
            0, 0, cell["traffic"]["seeds_per_request"]))
        grid = pack_specs(specs, tick=float(cell["config"]["tick_s"]))
        program, arrays = grid_program(grid, cached=False)
        args = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                     sharding=one_chip) for a in arrays]
        mem = program.lower(*args).compile().memory_analysis()
        print(json.dumps({
            "workload": w["name"], "lanes": grid.n_lanes,
            "ticks": grid.n_ticks, "K": grid.max_jobs_per_tick,
            "J": int(grid.job_fid.shape[2]),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
