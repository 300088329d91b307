"""The ``cfgIII-1M-gcs20.steady`` cell: cfg III under a 20 TB bucket quota.

Run on the CPU (the harness's look for a chip is skipped):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

* The cell resolves to cfg III's configuration with ``gcs_limit_tb`` 20
  and nothing else of the deployment changed.
* A run at 20,000 files per site reads ``correct`` under the cell's
  limits; the control (the reference with the link-slot guarantee
  broken) fails them at the cell's own catalogue size.
* ``metrics/gate_passes.py`` reads the gate's counter off a record, and
  reads nothing where the program keeps no counter.
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                "src"))

import control  # noqa: E402
import run  # noqa: E402

CELL = "cfgIII-1M-gcs20.steady"


def test_cell_is_cfg_iii_with_a_quota():
    cell = run.load_cell(CELL)
    base = run.load_cell("cfgIII-1M.steady")
    cfg, cfg3 = cell["config"], base["config"]
    assert cfg["gcs_limit_tb"] == 20 and cfg3["gcs_limit_tb"] is None
    own = {"name", "source", "deployment", "gcs_limit_tb", "guarantees",
           "assumed"}
    assert {k: v for k, v in cfg.items() if k not in own} == \
        {k: v for k, v in cfg3.items() if k not in own}
    assert cfg["guarantees"][:len(cfg3["guarantees"])] == cfg3["guarantees"]
    assert "gcs_limit_tb" in cfg["assumed"]
    assert cell["traffic"] == base["traffic"] and cell["chips"] == 1
    assert cell["limits"]["cell"] == CELL
    assert "gate_passes" in {m["name"] for m in cell["per_layer"]}


def _fails(cell, numbers):
    return [name for name, limit in cell["limits"]["limits"].items()
            if numbers[name] > limit]


def test_control_fails_at_cell_size():
    cell = run.load_cell(CELL)
    for r in (1, 2, 3):
        seeds = run.request_seeds(2 ** 31 + 5, r, 2)
        assert _fails(cell, control.control_numbers(cell, seeds))


def test_run_at_20k_files_is_correct():
    import jax

    cell = run.load_cell(CELL)
    cell["config"]["n_files"] = 20_000
    record = run.run_cell(cell, 2 ** 33 + 7, 0.1, False, jax.devices())
    checks = run.check(cell, record)
    line = run.result_line(cell, record, checks, False, jax.devices())
    assert line["correct"], line["checks"]
    for req in record["requests"]:
        for res in req["results"]:
            assert res.counters["gcs_refused_ticks"] > 0  # the quota binds
    assert run.load_reader("gate_passes")(record) > 0


def _record(results_per_request, ticks=361, ok=True):
    return {"requests": [{"ok": ok, "ticks": ticks,
                          "results": [SimpleNamespace(**r) for r in rs]}
                         for rs in results_per_request]}


@pytest.mark.parametrize("record, expect", [
    (_record([[{"counters": {"gcs_gate_passes": 361}},
               {"counters": {"gcs_gate_passes": 0}}],
              [{"counters": {"gcs_gate_passes": 722}},
               {"counters": {"gcs_gate_passes": 361}}]]), 1.0),
    (_record([[{"counters": {"gcs_gate_passes": 10}}]], ticks=20), 0.5),
    # a program without the counter (before it existed) reads nothing
    (_record([[{"metrics": {}}, {"metrics": {}}]]), None),
    (_record([[{"counters": {}}]]), None),
    # requests that failed are not counted; none left reads nothing
    (_record([[{"counters": {"gcs_gate_passes": 5}}]], ok=False), None),
])
def test_gate_passes_reader(record, expect):
    assert run.load_reader("gate_passes")(record) == expect
