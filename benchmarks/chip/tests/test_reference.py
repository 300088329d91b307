"""The plain reference reproduces the program's event engine bit for bit.

``reference.py`` is a copy of the event-driven engine written against the
benchmark's configuration files; on the same spec it must give the same
jobs, bytes, disk, waits and dollars as ``run_sweep(backend="process")``.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                "src"))

import reference  # noqa: E402
import run  # noqa: E402


@pytest.mark.parametrize("workload", ["cfgIII-1M.steady", "cfgII-1M.steady"])
@pytest.mark.parametrize("seed", [0, 7, 2 ** 40 + 3])
def test_reference_equals_event_engine(workload, seed):
    from repro.sim.sweep import run_scenario

    cell = run.load_cell(workload)
    cfg = dict(cell["config"], n_files=20_000)
    spec = run.request_specs(dict(cell, config=cfg), [seed])[0]
    prog = run_scenario(spec)
    ref = reference.simulate(cfg, cell["traffic"], seed)
    m = prog.metrics
    names = [s["name"] for s in cfg["sites"]]
    assert ref["jobs_done"] == m["jobs_done"]
    assert ref["download_b"] / 1e15 == m["download_pb"]
    assert ref["disk_to_gcs_b"] / 1e15 == m["disk_to_gcs_pb"]
    assert ref["gcs_to_disk_b"] / 1e15 == m["gcs_to_disk_pb"]
    assert ref["wait_h_mean"] == m["job_waiting_h_mean"]
    assert [d / 1e15 for d in ref["disk_used_b"]] == [
        m[f"{n}.disk_used_pb"] for n in names]
    usd = reference.bill(cfg["pricing"], spec.egress, ref["monthly"])
    assert (usd["storage_usd"], usd["network_usd"], usd["ops_usd"]) == (
        prog.storage_usd, prog.network_usd, prog.ops_usd)
