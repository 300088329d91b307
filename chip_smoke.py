#!/usr/bin/env python3
"""Chip smoke run: the batched sweep on a TPU at the paper's catalogue width.

    python chip_smoke.py              # one chip: full width + cross-check
    python chip_smoke.py --chips 4    # four chips: sharded vs unsharded only

Everything runs in this one process, through ``run_sweep(backend="jax")``,
the entry point a planner calls. There is no CPU path: the script exits
non-zero, and prints no result, unless JAX's devices are TPUs.

Phases (one chip):

1. Full width. Table 5 cfg III with S=2 sites and F=10^6 files per site
   (the paper's catalogue, ``core/hcdc.py``), Table 3/4 shapes, the 10 s
   clock. Grid: seeds 0-3 x ``cache_tb`` {50, 100} x ``egress``
   {internet, interconnect} = 16 configs on 8 dynamics lanes (egress only
   changes the bill). The one cut: the horizon is 0.25 days (2161 ticks)
   of the paper's 90, so that a run takes minutes. The grid runs twice;
   the second run must equal the first bitwise. Every result must be
   finite, finish jobs, and keep each site's disk within its cache limit.
2. Cross-check. The same grid at F=20,000 on the chip against the event
   engine (``backend="process"``, the reference) at the Table 2 bar (5%)
   on jobs finished and on throughput (bytes delivered to jobs).

With ``--chips 4`` only the four-chip phase runs: the full-width grid with
``shard=True`` over 4 devices against the unsharded program on one
device, compared bitwise.

Times printed here are one run each, not a benchmark. Results come back
to the host as numpy values, so every timed call has waited for the
device. The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; a failed phase exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FULL_FILES = 1_000_000
CHECK_FILES = 20_000
DAYS = 0.25
TICK = 10.0
#: Table 2 validation tolerance (fractional).
TABLE2_TOL = 0.05


class SmokeFailure(Exception):
    """A phase's check failed."""


def grid_specs(n_files: int, days: float):
    from repro.core.scenarios import expand_grid

    return expand_grid({"base": "III", "n_files": n_files, "days": days,
                        "seed": [0, 1, 2, 3], "cache_tb": [50.0, 100.0],
                        "egress": ["internet", "interconnect"]})


def say(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


def _bits(x):
    """Exact image of a result value for bitwise comparison."""
    if isinstance(x, float):
        return x.hex()
    if isinstance(x, dict):
        return tuple((k, _bits(v)) for k, v in sorted(x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_bits(v) for v in x)
    return x


def result_bits(res):
    return [(r.spec, _bits(r.metrics), _bits(r.storage_usd),
             _bits(r.network_usd), _bits(r.ops_usd), _bits(r.monthly))
            for r in res.results]


class CompileClock:
    """Counts XLA backend compiles and their seconds, as JAX reports them."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def take(self):
        out = (self.n, self.seconds)
        self.n, self.seconds = 0, 0.0
        return out


def timed_sweep(specs, clock, **kw):
    from repro.sim.sweep import run_sweep

    clock.take()
    t0 = time.perf_counter()
    res = run_sweep(specs, backend="jax", tick=TICK, **kw)
    wall = time.perf_counter() - t0
    n_compiles, compile_s = clock.take()
    if len(res.results) != len(specs) or not res.ok:
        raise SmokeFailure(f"sweep returned {len(res.results)} of "
                           f"{len(specs)} results ({res.failures})")
    return res, wall, n_compiles, compile_s


def describe_grid(specs) -> None:
    from repro.core.scenarios import pack_specs

    grid = pack_specs(specs, tick=TICK)
    say(f"grid: configs={grid.n_specs} L={grid.n_lanes} "
        f"S={len(grid.site_names)} F={grid.sizes.shape[2]} "
        f"ticks={grid.n_ticks} K={grid.max_jobs_per_tick} "
        f"J={grid.job_fid.shape[2]} tick={TICK:g}s days={specs[0].days:g}")


def check_results(res) -> None:
    for r in res.results:
        lbl = r.spec.label
        values = list(r.metrics.values()) + [r.storage_usd, r.network_usd,
                                             r.ops_usd]
        if not all(math.isfinite(v) for v in values):
            raise SmokeFailure(f"{lbl}: non-finite result")
        if not r.jobs_done > 0:
            raise SmokeFailure(f"{lbl}: no jobs finished")
        # The device counts bytes in float32; allow its rounding (1e-6).
        limit_pb = r.spec.cache_tb * 1e12 / 1e15
        for key, used in r.metrics.items():
            if key.endswith(".disk_used_pb") and used > limit_pb * (1 + 1e-6):
                raise SmokeFailure(f"{lbl}: {key}={used!r} over the "
                                   f"{limit_pb!r} PB cache limit")


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def full_width_phase(clock, device, n_files=FULL_FILES, days=DAYS):
    specs = grid_specs(n_files, days)
    describe_grid(specs)
    first, wall1, n1, c1 = timed_sweep(specs, clock)
    say(f"full width run 1 (one run, not a benchmark): wall={wall1!r}s "
        f"compiles={n1} compile_s={c1!r}")
    second, wall2, n2, c2 = timed_sweep(specs, clock)
    say(f"full width run 2 (warm, one run, not a benchmark): "
        f"wall={wall2!r}s compiles={n2} compile_s={c2!r}")
    say(f"peak_bytes_in_use={peak_bytes(device)}")
    if result_bits(first) != result_bits(second):
        raise SmokeFailure("full width: run 2 differs from run 1")
    say("check: run 2 bitwise equal to run 1: ok")
    check_results(first)
    jobs = [r.jobs_done for r in first.results]
    say(f"check: {len(jobs)} results finite, jobs finished "
        f"min={min(jobs)!r} max={max(jobs)!r}, disk within cache limit: ok")


def _rel(a, b, floor):
    return abs(a - b) / max(abs(a), abs(b), floor)


def cross_check_phase(clock, n_files=CHECK_FILES, days=DAYS):
    from repro.sim.sweep import run_sweep

    specs = grid_specs(n_files, days)
    describe_grid(specs)
    jx, wall, n, c = timed_sweep(specs, clock)
    say(f"cross-check jax (one run): wall={wall!r}s compiles={n} "
        f"compile_s={c!r}")
    t0 = time.perf_counter()
    ref = run_sweep(specs, backend="process", workers=1)
    say(f"cross-check event engine (one run, host): "
        f"wall={time.perf_counter() - t0!r}s")
    bad = []
    worst_jobs = worst_tput = 0.0
    for a, b in zip(ref.results, jx.results):
        ta, tb = a.metrics["download_pb"], b.metrics["download_pb"]
        dj, dt = _rel(a.jobs_done, b.jobs_done, 1.0), _rel(ta, tb, 1e-6)
        worst_jobs, worst_tput = max(worst_jobs, dj), max(worst_tput, dt)
        if dj > TABLE2_TOL or dt > TABLE2_TOL:
            bad.append(f"{a.spec.label}: jobs {a.jobs_done!r} vs "
                       f"{b.jobs_done!r}, download_pb {ta!r} vs {tb!r}")
    say(f"cross-check: worst jobs-finished diff={worst_jobs!r}, worst "
        f"throughput diff={worst_tput!r} (bar {TABLE2_TOL})")
    if bad:
        raise SmokeFailure("cross-check over the Table 2 bar:\n  "
                           + "\n  ".join(bad))
    say("check: cross-check within the Table 2 bar: ok")


def four_chip_phase(clock, devices, n_files=FULL_FILES, days=DAYS):
    specs = grid_specs(n_files, days)
    describe_grid(specs)
    one, wall1, n1, c1 = timed_sweep(specs, clock, devices=devices[:1])
    say(f"unsharded on 1 device (one run): wall={wall1!r}s compiles={n1} "
        f"compile_s={c1!r}")
    four, wall4, n4, c4 = timed_sweep(specs, clock, shard=True)
    say(f"shard=True over {len(devices)} devices (one run): wall={wall4!r}s "
        f"compiles={n4} compile_s={c4!r}")
    four_warm, wall4w, _, _ = timed_sweep(specs, clock, shard=True)
    say(f"shard=True warm (one run): wall={wall4w!r}s")
    for d in devices:
        say(f"device {d.id} peak_bytes_in_use={peak_bytes(d)}")
    if result_bits(one) != result_bits(four) \
            or result_bits(four) != result_bits(four_warm):
        raise SmokeFailure("sharded results differ from the unsharded ones")
    check_results(four)
    say("check: sharded bitwise equal to unsharded: ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-vs-unsharded phase")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.sim.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r}); there is no "
              "CPU path", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    say(f"device_kind={devices[0].device_kind!r} count={len(jax.devices())}")
    say(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    try:
        if args.chips == 4:
            four_chip_phase(clock, devices)
        else:
            full_width_phase(clock, devices[0])
            cross_check_phase(clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
