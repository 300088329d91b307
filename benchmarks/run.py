"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus per-row comparison
columns where the paper provides reference values).

  table2   bench_validation   (simulation correctness, 5 metrics)
  table6/7 bench_hcdc         (jobs done, volumes for cfg I/II/III)
  table8   bench_cost         (monthly GCS cost, cfg III)
  sweep    bench_sweep        (scenario-sweep engine: process configs/sec
                               + batched-backend lanes/sec)
  fleet    bench_fleet        (worker-fleet lane scaling: 1024/10k-lane
                               grids across a workers axis + bitwise
                               parity gate vs the serial registry path)
  roofline bench_roofline     (dry-run roofline terms per cell)

Env knobs: HCDC_RUNS (default 1), HCDC_DAYS (90), HCDC_FILES (1e6),
VALIDATION_RUNS (2), SWEEP_CONFIGS (8), FAST=1 (reduced scales for CI
smoke), BENCH_JSON=path (also write every row as a JSON document with
name/us_per_call/derived fields — the CI perf-trajectory artifact).

A bench module that raises does not abort the remaining modules, but the
runner exits non-zero so CI catches the breakage.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback
from typing import Dict, List

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; make `from benchmarks import bench_*` work from anywhere.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from repro.sim.compile_cache import use_compile_cache

    use_compile_cache()
    fast = os.environ.get("FAST", "0") == "1"
    t0 = time.time()
    collected: List[Dict] = []
    failures: List[str] = []

    def section(name, fn):
        """Run one bench module; record rows, keep going on failure."""
        try:
            rows = fn()
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"# BENCH FAILED: {name}", flush=True)
            return
        collected.extend(rows)

    def validation():
        from benchmarks import bench_validation
        runs = int(os.environ.get("VALIDATION_RUNS", "1" if fast else "2"))
        horizon = 2.0 if fast else None
        rows = bench_validation.run(n_runs=runs, horizon_days=horizon)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']:.4g},"
                  f"paper={r['paper']:.4g},diff={r['diff_pct']:+.2f}%",
                  flush=True)
        return rows

    section("validation", validation)

    hruns = int(os.environ.get("HCDC_RUNS", "1"))
    days = int(os.environ.get("HCDC_DAYS", "5" if fast else "90"))
    files = int(os.environ.get("HCDC_FILES", "50000" if fast else "1000000"))

    def hcdc():
        from benchmarks import bench_hcdc
        rows = bench_hcdc.run(n_runs=hruns, days=days, n_files=files)
        for r in rows:
            ref = (f",paper={r['paper']:.4g},diff={r['diff_pct']:+.2f}%"
                   if r.get("paper") else "")
            print(f"{r['name']},{r['us_per_call']:.0f},"
                  f"{r['derived']:.4g}{ref}", flush=True)
        return rows

    section("hcdc", hcdc)

    def cost():
        from benchmarks import bench_cost
        rows = bench_cost.run(n_runs=hruns, days=days, n_files=files)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']:.4g},"
                  f"paper={r['paper']:.4g},diff={r['diff_pct']:+.2f}%",
                  flush=True)
        return rows

    section("cost", cost)

    def sweep():
        from benchmarks import bench_sweep
        sweep_cfgs = int(os.environ.get("SWEEP_CONFIGS", "4" if fast else "8"))
        rows = bench_sweep.run(n_configs=sweep_cfgs,
                               days=0.1 if fast else 0.25, fast=fast)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']:.4g}",
                  flush=True)
        return rows

    section("sweep", sweep)

    def fleet():
        from benchmarks import bench_fleet
        rows = bench_fleet.run(fast=fast)
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.0f},{r['derived']:.4g}",
                  flush=True)
        return rows

    section("fleet", fleet)

    def roofline():
        from benchmarks import bench_roofline
        rows = bench_roofline.run()
        for r in rows:
            extra = ""
            if "dominant" in r:
                extra = (f",dom={r['dominant']},c={r['compute_s']:.3f}s,"
                         f"m={r['memory_s']:.3f}s,"
                         f"coll={r['collective_s']:.3f}s,"
                         f"useful={r['useful']:.3f}")
            d = r["derived"]
            d_str = f"{d:.4f}" if isinstance(d, float) else str(d)
            print(f"{r['name']},{r['us_per_call']:.0f},{d_str}{extra}",
                  flush=True)
        return rows

    section("roofline", roofline)

    wall = time.time() - t0
    print(f"# total benchmark wall time: {wall:.1f}s")

    json_path = os.environ.get("BENCH_JSON", "")
    if json_path:
        doc = {
            "wall_s": wall,
            "fast": fast,
            "failures": failures,
            "benches": [
                {"name": r["name"],
                 "us_per_call": float(r["us_per_call"]),
                 "derived": (float(r["derived"])
                             if isinstance(r["derived"], (int, float))
                             else str(r["derived"]))}
                for r in collected
            ],
        }
        if os.path.dirname(json_path):
            os.makedirs(os.path.dirname(json_path), exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {json_path} ({len(collected)} rows)")

    if failures:
        print(f"# FAILED benches: {', '.join(failures)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
