"""Unit tests for the ``repro.obs`` telemetry layer (ISSUE 8)."""

import json
import os
import subprocess
import sys

import pytest

from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    get_registry,
    snapshot_and_reset,
    split_series_name,
)
from repro.obs.trace import Tracer, get_tracer, jax_device_profile


# ------------------------------------------------------------------ metrics
class TestMetricsRegistry:
    def test_counter_inc_and_value(self):
        r = MetricsRegistry()
        r.inc("cache.hits")
        r.inc("cache.hits", 2.0)
        assert r.value("cache.hits") == 3.0
        assert r.value("cache.misses") == 0.0  # default

    def test_labels_are_sorted_into_one_series(self):
        r = MetricsRegistry()
        r.inc("x", b="2", a="1")
        r.inc("x", a="1", b="2")
        snap = r.snapshot()
        assert snap["counters"] == {"x{a=1,b=2}": 2.0}

    def test_split_series_name_round_trip(self):
        assert split_series_name("x{a=1,b=2}") == ("x", {"a": "1",
                                                        "b": "2"})
        assert split_series_name("plain") == ("plain", {})

    def test_gauge_last_write_wins(self):
        r = MetricsRegistry()
        r.set_gauge("lanes.simulated", 5)
        r.set_gauge("lanes.simulated", 0)
        assert r.value("lanes.simulated") == 0.0

    def test_histogram_observe(self):
        r = MetricsRegistry()
        for v in (0.002, 0.2, 100.0):
            r.observe("wall_s", v)
        h = r.snapshot()["histograms"]["wall_s"]
        assert h["count"] == 3
        assert h["sum"] == pytest.approx(100.202)
        assert sum(h["counts"]) == 3
        assert h["counts"][-1] == 1  # 100.0 lands in +Inf
        assert h["bounds"] == list(DEFAULT_BUCKETS)

    def test_disabled_registry_records_nothing(self):
        r = MetricsRegistry(enabled=False)
        r.inc("a")
        r.set_gauge("b", 1.0)
        r.observe("c", 1.0)
        snap = r.snapshot()
        assert snap == {"counters": {}, "gauges": {}, "histograms": {}}

    def test_merge_worker_delta(self):
        """The pool round trip: worker snapshot deltas fold into the
        parent — counters/histograms add, gauges assign."""
        parent, worker = MetricsRegistry(), MetricsRegistry()
        parent.inc("scenario.runs", 2)
        parent.observe("wall_s", 1.0)
        worker.inc("scenario.runs", 3)
        worker.set_gauge("lanes.simulated", 7)
        worker.observe("wall_s", 2.0)
        delta = snapshot_and_reset(worker)
        assert worker.snapshot()["counters"] == {}  # reset cleared it
        parent.merge(delta)
        assert parent.value("scenario.runs") == 5.0
        assert parent.value("lanes.simulated") == 7.0
        h = parent.snapshot()["histograms"]["wall_s"]
        assert h["count"] == 2 and h["sum"] == pytest.approx(3.0)

    def test_merge_into_disabled_registry_still_lands(self):
        # merge() is bookkeeping, not new measurement: a parent that
        # disabled collection still folds worker deltas faithfully.
        parent = MetricsRegistry(enabled=False)
        parent.merge({"counters": {"a": 1.0}})
        assert parent.value("a") == 1.0
        assert parent.enabled is False

    def test_prometheus_exposition(self):
        r = MetricsRegistry()
        r.inc("cache.hits", 3, help="Result-cache lookup hits")
        r.inc("tick_impl.resolved", impl="jnp")
        r.observe("wall_s", 0.3)
        text = r.to_prometheus()
        assert "# HELP cache_hits Result-cache lookup hits" in text
        assert "# TYPE cache_hits counter" in text
        assert "cache_hits 3" in text
        assert 'tick_impl_resolved{impl="jnp"} 1' in text
        assert 'wall_s_bucket{le="+Inf"} 1' in text
        assert "wall_s_count 1" in text

    def test_dump_json_vs_prometheus(self, tmp_path):
        r = MetricsRegistry()
        r.inc("a", 2)
        jpath, ppath = tmp_path / "m.json", tmp_path / "m.prom"
        r.dump(str(jpath))
        r.dump(str(ppath))
        doc = json.loads(jpath.read_text())
        assert doc["counters"] == {"a": 2.0}
        assert "exported_unix" in doc
        assert "# TYPE a counter" in ppath.read_text()

    def test_global_registry_is_a_singleton(self):
        assert get_registry() is get_registry()


# -------------------------------------------------------------------- trace
class TestTracer:
    def test_disabled_span_records_nothing(self):
        tr = Tracer()
        with tr.span("phase"):
            pass
        assert tr.events == []

    def test_enabled_span_records_complete_event(self):
        tr = Tracer(run_id="abc", enabled=True)
        with tr.span("simulate", lanes=4):
            pass
        (ev,) = tr.events
        assert ev["name"] == "simulate" and ev["ph"] == "X"
        assert ev["dur"] >= 1
        assert ev["args"] == {"lanes": 4, "run_id": "abc"}

    def test_span_annotates_and_propagates_exceptions(self):
        tr = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        (ev,) = tr.events
        assert ev["args"]["error"] is True

    def test_chrome_dict_and_dump(self, tmp_path):
        tr = Tracer(run_id="rid1", enabled=True)
        with tr.span("a"):
            pass
        tr.instant("marker", note="hi")
        path = tmp_path / "trace.json"
        tr.dump(str(path))
        doc = json.loads(path.read_text())
        assert doc["otherData"]["run_id"] == "rid1"
        names = [e["name"] for e in doc["traceEvents"]]
        assert names == ["a", "marker"]

    def test_enable_sets_run_id_and_reset_clears(self):
        tr = Tracer()
        tr.enable(run_id="zz")
        assert tr.enabled and tr.run_id == "zz"
        with tr.span("a"):
            pass
        tr.reset()
        assert tr.events == []

    def test_global_tracer_disabled_by_default(self):
        assert get_tracer() is get_tracer()

    @pytest.mark.parametrize("switch", ["enabled", "never", "disabled"])
    def test_span_opens_a_profiler_annotation_only_when_enabled(
            self, monkeypatch, switch):
        import jax.profiler

        opened = []

        class Counting:
            def __init__(self, name, **kwargs):
                opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
        tr = Tracer()
        if switch != "never":
            tr.enable()
        if switch == "disabled":
            tr.disable()
        with tr.span("pack_specs"):
            with tr.span("simulate_packed", lanes=2):
                pass
        expected = ["pack_specs", "simulate_packed"]
        assert opened == (expected if switch == "enabled" else [])
        # the inner span closes, and is recorded, first
        assert [e["name"] for e in tr.events] == (
            expected[::-1] if switch == "enabled" else [])

    def test_importing_repro_obs_leaves_jax_unimported(self):
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-c",
             "import sys, repro.obs; print('jax' in sys.modules)"],
            env=env, capture_output=True, text=True, check=True)
        assert out.stdout.strip() == "False"

    def test_batched_sweep_spans_pack_dispatch_and_fold(self):
        from repro.core.scenarios import ScenarioSpec
        from repro.sim.batched import run_sweep_jax

        tr = get_tracer()
        tr.reset()
        tr.enable()
        try:
            res = run_sweep_jax([ScenarioSpec(base="III", days=0.02,
                                              n_files=200, seed=s)
                                 for s in (1, 2)], tick=60.0)
        finally:
            tr.disable()
        spans = {e["name"]: e for e in tr.events if e["ph"] == "X"}
        tr.reset()
        assert res.ok and len(res.results) == 2
        fold = spans["fold_results"]
        assert fold["args"]["specs"] == 2
        assert fold["ts"] >= spans["simulate_packed"]["ts"] \
            + spans["simulate_packed"]["dur"] - 1

    def test_jax_device_profile_noop_when_disabled(self):
        # tracer disabled -> silent no-op even with a logdir
        with jax_device_profile("/tmp/never-used"):
            pass
        with jax_device_profile(None):
            pass
