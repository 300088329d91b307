"""The trace reduction, on a small profiler trace recorded on a TPU v5e.

``testdata/two_requests.xplane.pb`` holds two warm requests of a cut-down
cell (2,000 files per site, 0.02 days), each wrapped in a
``bench.request`` annotation; ``two_requests.host.json`` holds the
repository tracer's spans and the ``perf_counter_ns`` reading taken
inside the first annotation; ``two_requests.expected.json`` is the
reduction's output, checked by hand against the trace when recorded.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
DATA = os.path.join(BENCH, "testdata")
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _reduced():
    host = _load("two_requests.host.json")
    reqs, devs = trace_reduce.read_trace(
        os.path.join(DATA, "two_requests.xplane.pb"))
    return trace_reduce.reduce(reqs, devs, host["spans"],
                               host["perf_at_first_ns"])


def test_reduction_matches_expected():
    assert _reduced() == _load("two_requests.expected.json")


def test_busy_time_is_inside_the_window():
    out = _reduced()
    assert 0 < out["busy_s"] <= out["window_s"]
    assert sum(s for _, s in out["idle_gaps"]) <= out["window_s"] - out["busy_s"] + 1e-9


def test_no_request_or_no_device_op_gives_nothing():
    assert trace_reduce.reduce([], {"/device:TPU:0": [(0, 5, "op")]}) is None
    assert trace_reduce.reduce([(0, 10)], {"/device:TPU:0": []}) is None


def test_union_and_gap_attribution():
    # in a request from 0 to 10 us on the profiler's clock: a loop op
    # "w" from 1 to 5 us holding "b" from 3 to 4 us, then "b" from 8 to
    # 9 us; a pack span from 6.5 to 8.5 us on the host clock, which the
    # first request's perf_counter reading (1 us at profiler time 0)
    # places at 5.5 to 7.5 us
    reqs = [(0, 10_000)]
    devs = {"/device:TPU:0": [(1_000, 5_000, "w"), (3_000, 4_000, "b"),
                              (8_000, 9_000, "b")]}
    spans = [{"name": "pack_specs", "ts": 6.5, "dur": 2}]
    out = trace_reduce.reduce(reqs, devs, spans, perf_at_first_ns=1_000)
    assert out["busy_s"] == 5e-6
    assert out["device_ops"] == [["w", 3e-6], ["b", 2e-6]]
    assert out["idle_gaps"] == [["pack_specs", 2e-6], ["fold", 1e-6],
                                ["fold", 1e-6], ["fold", 0.5e-6],
                                ["fold", 0.5e-6]]
