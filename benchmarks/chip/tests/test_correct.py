"""``correct`` comes out false for the control and for each fault.

Run on the CPU (the harness's look for a chip is skipped):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

* The control (the reference with the disk-limit guarantee broken, in
  the program's place) fails a cell's limits, at the cell's own
  catalogue size: it is plain numpy, so its numbers here are the ones it
  gives on the chip's host.
* The rest of a run, driven through ``run.run_cell`` and ``run.check``
  at a catalogue of 20,000 files per site, with the timed path broken
  under ``run_sweep``: a tick that returns its state unchanged, half of
  the lanes left out, and an answer altered where it is produced (one
  lane's jobs, one lane's bill). The sound run at this size is correct.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                "src"))

import control  # noqa: E402
import run  # noqa: E402

CELLS = ("cfgIII-1M.steady", "cfgII-1M.steady")
TEST_FILES = 20_000


def _fails(cell, numbers):
    return [name for name, limit in cell["limits"]["limits"].items()
            if numbers[name] > limit]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_at_cell_size(workload):
    cell = run.load_cell(workload)
    for r in (1, 2, 3):
        seeds = run.request_seeds(2 ** 31 + 5, r, 2)
        assert _fails(cell, control.control_numbers(cell, seeds))


def _small(workload):
    cell = run.load_cell(workload)
    cell["config"]["n_files"] = TEST_FILES
    return cell


def _broken_sweep(monkeypatch, alter):
    """``run_sweep`` with ``alter(out)`` applied to the device's output."""
    from repro.sim import batched

    real = batched.simulate_packed

    def simulate_packed(grid, **kw):
        out = {k: np.array(v) for k, v in real(grid, **kw).items()}
        alter(out)
        return out

    monkeypatch.setattr(batched, "simulate_packed", simulate_packed)


def _unchanged(out):
    for k, v in out.items():
        v[...] = 0


def _half_left_out(out):
    for v in out.values():
        v[v.shape[0] // 2:] = 0


def _jobs_altered(out):
    jobs = out["jobs_done_site"]
    jobs[0] = (jobs[0] * 5) // 4


def _run(workload):
    import jax

    cell = _small(workload)
    record = run.run_cell(cell, 2 ** 33 + 7, 0.1, False, jax.devices())
    checks = run.check(cell, record)
    return run.result_line(cell, record, checks, False, jax.devices())


def test_sound_run_is_correct():
    line = _run("cfgIII-1M.steady")
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out, _jobs_altered])
def test_fault_is_not_correct(monkeypatch, fault):
    _broken_sweep(monkeypatch, fault)
    line = _run("cfgIII-1M.steady")
    assert not line["correct"], line["checks"]


def test_altered_bill_is_not_correct(monkeypatch):
    from repro.sim import batched

    real = batched.bills_from_monthly_totals

    def bills(*a, **kw):
        out = real(*a, **kw)
        out[0].storage_usd *= 1 + 1e-9
        return out

    monkeypatch.setattr(batched, "bills_from_monthly_totals", bills)
    line = _run("cfgIII-1M.steady")
    assert not line["correct"], line["checks"]
