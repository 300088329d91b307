"""ISSUE 6: persistent content-addressed result cache + provenance store.

Correctness-first battery for ``repro.sim.cache``:

- key semantics: invariant under pricing-only field changes, distinct for
  every dynamics-affecting change, engine-fingerprinted, stable across
  process restarts (the hypothesis properties live in
  ``tests/test_property.py``);
- bit-exact round trips on both engines, including pricing variants
  re-billed from a shared dynamics entry;
- adversarial durability: truncated/zero-byte/garbage/wrong-schema-version
  entries fall back to recompute (never crash, never serve bad data) and
  the repaired entry is rewritten; concurrent same-key writers publish
  one valid entry;
- end-to-end warm-cache accounting through ``run_sweep(cache=...)``,
  ``SweepDriver(cache=...)``, ``decide()``, and the 216-config
  ``scripts/decide.py`` grid (``lanes_simulated == 0`` on re-run).
"""

from __future__ import annotations

import importlib.util
import json
import multiprocessing
import os
import subprocess
import sys

import pytest

from repro.core.scenarios import (
    RESULT_SCHEMA_VERSION,
    ScenarioSpec,
    cache_key,
    engine_fingerprint,
    expand_grid,
    with_axis,
    with_seeds,
)
from repro.sim.cache import (
    LocalDirBackend,
    ResultCache,
    as_cache,
    entry_name,
)
from repro.sim.decide import decide
from repro.sim.sweep import SweepDriver, run_scenario, run_sweep

#: Smallest spec that still exercises cache dynamics + billing.
TINY = dict(base="III", days=0.05, n_files=300, cache_tb=5.0)

#: Quick cross-backend parity grid (2 lanes x 2 pricing x 2 seeds).
QUICK_AXES = {"base": "III", "days": 0.1, "n_files": 1000,
              "cache_tb": [5.0, 20.0], "egress": ["internet", "direct"]}


@pytest.fixture(scope="module")
def tiny_result():
    """One freshly simulated (spec, result) pair, shared by the battery."""
    spec = ScenarioSpec(**TINY)
    return spec, run_scenario(spec)


def _entry_path(root, spec, backend="process", tick=None) -> str:
    return os.path.join(str(root),
                        entry_name(cache_key(spec, backend=backend,
                                             tick=tick)))


def _same_result(a, b) -> None:
    """Bitwise equality of everything a sweep consumer can observe."""
    assert a.spec == b.spec
    assert a.metrics == b.metrics
    assert (a.storage_usd, a.network_usd, a.ops_usd) == \
        (b.storage_usd, b.network_usd, b.ops_usd)
    assert a.events == b.events
    assert a.series == b.series
    assert a.monthly == b.monthly


# ------------------------------------------------------------ key semantics
def test_cache_key_invariant_under_pricing_fields():
    spec = ScenarioSpec(**TINY)
    for field, value in [("egress", "direct"), ("egress", "interconnect"),
                         ("storage_price", 0.020), ("egress_price", 0.01)]:
        assert cache_key(with_axis(spec, "cache_tb", 5.0)) == \
            cache_key(spec)  # identity sanity
        variant = ScenarioSpec(**{**TINY, field: value})
        assert cache_key(variant) == cache_key(spec), field
        assert cache_key(variant, "jax", 60.0) == \
            cache_key(spec, "jax", 60.0), field


def test_cache_key_distinct_for_every_dynamics_field():
    spec = ScenarioSpec(**TINY)
    base_key = cache_key(spec)
    for field, value in [("base", "I"), ("days", 0.1), ("n_files", 500),
                         ("seed", 1), ("cache_tb", 10.0),
                         ("gcs_limit_tb", 50.0), ("job_rate_scale", 2.0),
                         ("workload", "diurnal"), ("curves", True)]:
        variant = ScenarioSpec(**{**TINY, field: value})
        assert cache_key(variant) != base_key, field


def test_cache_key_fingerprints_the_engine():
    spec = ScenarioSpec(**TINY)
    keys = {cache_key(spec, "process"), cache_key(spec, "jax", 10.0),
            cache_key(spec, "jax", 60.0)}
    assert len(keys) == 3  # engines and tick steps never cross-serve
    # the process engine is tick-free; jax defaults to the 10 s tick
    assert cache_key(spec, "process", 60.0) == cache_key(spec, "process")
    assert cache_key(spec, "jax", None) == cache_key(spec, "jax", 10.0)
    assert engine_fingerprint("jax", 60.0) == "jax:60"
    with pytest.raises(ValueError):
        engine_fingerprint("cuda")


def test_jax_fingerprint_is_the_legacy_key():
    """The batched engine's fingerprint is ``"jax:<tick>"``, and its key
    the hash of the same canonical document as ever, so every entry
    already stored for the batched engine keeps its key."""
    import hashlib
    from dataclasses import asdict

    from repro.core.scenarios import dynamics_key

    assert engine_fingerprint("jax", 60.0) == "jax:60"
    assert engine_fingerprint("jax") == "jax:10"
    assert engine_fingerprint("process") == "process"
    spec = ScenarioSpec(**TINY)
    doc = {"schema": RESULT_SCHEMA_VERSION, "engine": "jax:60",
           "spec": asdict(dynamics_key(spec))}
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    assert cache_key(spec, "jax", 60.0) == \
        hashlib.sha256(blob.encode("utf-8")).hexdigest()


def test_entry_written_with_tick_impl_metadata_still_hits(tmp_path,
                                                          tiny_result):
    """Entries of the batched engine stored with a ``"tick_impl": "jnp"``
    field in their manifest are served as they are."""
    spec, result = tiny_result
    cache = ResultCache(tmp_path)
    assert cache.put(spec, result, backend="jax", tick=60.0)
    path = _entry_path(tmp_path, spec, backend="jax", tick=60.0)
    with open(path) as f:
        doc = json.load(f)
    assert "tick_impl" not in doc["manifest"]
    doc["manifest"]["tick_impl"] = "jnp"
    with open(path, "w") as f:
        json.dump(doc, f)
    served = ResultCache(tmp_path).get(spec, backend="jax", tick=60.0)
    assert served is not None
    _same_result(served, result)


def test_cache_key_stable_across_process_restart():
    """Keys are pure content hashes: a fresh interpreter (fresh PYTHONHASHSEED)
    derives the same key for the same spec."""
    spec = ScenarioSpec(**{**TINY, "seed": 3})
    code = ("from repro.core.scenarios import ScenarioSpec, cache_key; "
            f"print(cache_key(ScenarioSpec(base='III', days={TINY['days']}, "
            f"n_files={TINY['n_files']}, cache_tb={TINY['cache_tb']}, "
            "seed=3), backend='jax', tick=60.0))")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONHASHSEED"] = "random"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, check=True, timeout=120)
    assert out.stdout.strip() == cache_key(spec, backend="jax", tick=60.0)


# ------------------------------------------------- round trips (bit-exact)
def test_roundtrip_is_bitwise_on_process_backend(tmp_path, tiny_result):
    spec, fresh = tiny_result
    cache = ResultCache(tmp_path)
    assert cache.put(spec, fresh)
    served = ResultCache(tmp_path).get(spec)  # fresh instance: disk only
    assert served is not None
    _same_result(served, fresh)
    assert served.wall_s == fresh.wall_s  # provenance carries the cost paid


def test_pricing_variant_served_from_shared_entry_is_bitwise(tmp_path,
                                                             tiny_result):
    spec, fresh = tiny_result
    cache = ResultCache(tmp_path)
    cache.put(spec, fresh)
    for field, value in [("egress", "direct"), ("egress_price", 0.01),
                         ("storage_price", 0.020)]:
        variant = ScenarioSpec(**{**TINY, field: value})
        served = cache.get(variant)
        assert served is not None, field  # same dynamics entry serves it
        _same_result(served, run_scenario(variant))
    assert cache.stats.writes == 1  # one lane entry served four ways


def test_roundtrip_is_bitwise_on_jax_backend(tmp_path):
    specs = with_seeds(expand_grid(QUICK_AXES), 2)
    fresh = run_sweep(specs, backend="jax", tick=60.0)
    cache = ResultCache(tmp_path)
    assert cache.store(zip(specs, fresh.results),
                       backend="jax", tick=60.0) == 4  # lanes, not configs
    for spec, r in zip(specs, fresh.results):
        _same_result(cache.get(spec, backend="jax", tick=60.0), r)


def test_engine_entries_never_cross_serve(tmp_path, tiny_result):
    spec, fresh = tiny_result
    cache = ResultCache(tmp_path)
    cache.put(spec, fresh, backend="process")
    assert cache.get(spec, backend="jax", tick=60.0) is None
    assert cache.get(spec, backend="jax", tick=10.0) is None
    assert cache.get(spec, backend="process") is not None


def test_synthetic_results_are_never_stored(tmp_path, tiny_result):
    """Results without raw monthly totals (hand-built, never simulated)
    cannot be re-billed and must not populate the store."""
    from repro.sim.sweep import ScenarioResult

    spec, _ = tiny_result
    fake = ScenarioResult(spec=spec, metrics={"jobs_done": 1.0},
                          storage_usd=0.0, network_usd=0.0, ops_usd=0.0,
                          wall_s=0.0, events=0)
    cache = ResultCache(tmp_path)
    assert not cache.put(spec, fake)
    assert cache.store([(spec, fake)]) == 0
    assert cache.get(spec) is None


def test_entry_manifest_records_provenance(tmp_path, tiny_result):
    spec, fresh = tiny_result
    ResultCache(tmp_path).put(spec, fresh)
    doc = json.loads(open(_entry_path(tmp_path, spec)).read())
    assert doc["schema_version"] == RESULT_SCHEMA_VERSION
    man = doc["manifest"]
    assert man["engine"] == "process"
    assert man["spec"]["egress"] == "internet"  # dynamics key, not variants
    assert man["spec"]["cache_tb"] == TINY["cache_tb"]
    for field in ("package_version", "python", "numpy", "host",
                  "created_unix", "wall_s"):
        assert field in man, field


# ------------------------------------------------------------- durability
def _truncate(path):
    data = open(path, "rb").read()
    open(path, "wb").write(data[:len(data) // 2])


def _zero(path):
    open(path, "wb").close()


def _garbage(path):
    open(path, "wb").write(b"\x00\xffnot json at all {{{")


def _wrong_version(path):
    doc = json.load(open(path))
    doc["schema_version"] = RESULT_SCHEMA_VERSION + 999
    json.dump(doc, open(path, "w"))


def _mangled_payload(path):
    doc = json.load(open(path))
    doc["payload"]["monthly"]["egress_bytes"] = doc["payload"]["monthly"][
        "egress_bytes"] + [1.0]  # array lengths disagree
    json.dump(doc, open(path, "w"))


@pytest.mark.parametrize("mangle", [_truncate, _zero, _garbage,
                                    _wrong_version, _mangled_payload],
                         ids=["truncated", "zero-byte", "garbage",
                              "wrong-schema-version", "mangled-payload"])
def test_corrupted_entry_falls_back_to_recompute(tmp_path, tiny_result,
                                                 mangle):
    spec, fresh = tiny_result
    ResultCache(tmp_path).put(spec, fresh)
    path = _entry_path(tmp_path, spec)
    mangle(path)
    cache = ResultCache(tmp_path)
    assert cache.get(spec) is None  # never crash, never serve bad data
    assert cache.stats.corrupt == 1 and cache.stats.hits == 0
    assert not os.path.exists(path)  # bad entry dropped...
    res = run_sweep([spec], workers=1, cache=cache)  # ...recompute repairs
    assert res.lanes_simulated == 1 and res.cache_hits == 0
    _same_result(res.results[0], fresh)
    assert os.path.exists(path)
    served = cache.get(spec)
    assert served is not None
    _same_result(served, fresh)


def _put_loop(cache_dir, spec, result, n):
    from repro.sim.cache import ResultCache

    cache = ResultCache(cache_dir)
    for _ in range(n):
        cache.put(spec, result)


def test_concurrent_writers_publish_one_valid_entry(tmp_path, tiny_result):
    """Two processes hammering the same key: every read along the way sees
    a complete entry (write-to-temp + atomic rename), and exactly one
    published file remains."""
    spec, fresh = tiny_result
    ctx = multiprocessing.get_context("spawn")
    procs = [ctx.Process(target=_put_loop,
                         args=(str(tmp_path), spec, fresh, 20))
             for _ in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=180)
    assert all(p.exitcode == 0 for p in procs)
    names = sorted(LocalDirBackend(str(tmp_path)).names())
    assert names == [entry_name(cache_key(spec))]
    served = ResultCache(tmp_path).get(spec)
    assert served is not None
    _same_result(served, fresh)
    # no half-written temp files survive a clean run
    leftovers = [f for _, _, fs in os.walk(tmp_path) for f in fs
                 if ".tmp." in f]
    assert leftovers == []


# ----------------------------------------------- end-to-end warm accounting
def test_run_sweep_get_or_compute_accounting(tmp_path):
    specs = with_seeds([ScenarioSpec(**TINY)], 2)
    cold = run_sweep(specs, workers=1, cache=str(tmp_path))
    assert cold.lanes_simulated == 2 and cold.cache_hits == 0
    warm = run_sweep(specs, workers=1, cache=str(tmp_path))
    assert warm.lanes_simulated == 0 and warm.cache_hits == 2
    for a, b in zip(cold.results, warm.results):
        _same_result(a, b)
    # a never-requested pricing variant rides a stored dynamics lane
    priced = with_axis(specs[0], "egress_price", 0.01)
    res = run_sweep([priced], workers=1, cache=str(tmp_path))
    assert res.cache_hits == 1 and res.lanes_simulated == 0
    _same_result(res.results[0], run_scenario(priced))


@pytest.mark.parametrize("backend,tick", [("process", 10.0), ("jax", 60.0)])
def test_warm_driver_rerun_is_bitwise_and_simulates_nothing(tmp_path,
                                                            backend, tick):
    """The quick cross-backend parity grid twice through ``SweepDriver``
    with a tmpdir cache: the second (fresh) driver simulates zero lanes
    and reproduces the cold ``SweepResult`` bit-exactly."""
    specs = with_seeds(expand_grid(QUICK_AXES), 2)
    kw = dict(backend=backend, tick=tick, workers=1, cache=str(tmp_path))
    cold_drv = SweepDriver(**kw)
    cold = cold_drv.run(specs)
    assert cold_drv.lanes_simulated == 4  # 2 cache sizes x 2 seeds
    assert cold.cache_hits == 0
    warm_drv = SweepDriver(**kw)  # fresh driver: empty memo, disk only
    warm = warm_drv.run(specs)
    assert warm.lanes_simulated == 0
    assert warm.cache_hits == len(set(specs))
    assert warm_drv.configs_run == 0 and warm_drv.lanes_simulated == 0
    for a, b in zip(cold.results, warm.results):
        _same_result(a, b)


def test_driver_cache_serves_late_pricing_variants(tmp_path):
    """The in-memory memo re-simulates pricing variants that arrive in a
    later round (``pack_specs`` dedups within one call only); the
    persistent cache serves them from the stored lane instead."""
    specs = with_seeds([ScenarioSpec(**TINY)], 2)
    driver = SweepDriver(backend="process", workers=1, cache=str(tmp_path))
    driver.run(specs)
    assert driver.lanes_simulated == 2 and driver.configs_run == 2
    priced = with_axis(specs[0], "egress_price", 0.01)
    res = driver.run([priced])
    assert driver.lanes_simulated == 2  # no new lane simulated
    assert driver.configs_run == 2  # no new config simulated
    assert res.cache_hits == 1 and driver.cache_hits == 1
    _same_result(res.results[0], run_scenario(priced))


def test_warm_decide_workflow_simulates_zero_lanes(tmp_path):
    """A full ``decide()`` workflow re-run on a warm cache — refinement
    rounds, displaced-disk bisection, break-even pricing probes — answers
    everything from disk: the warm run's probe sequence is identical
    because every served result is bitwise identical."""
    axes = {"base": "III", "days": 0.05, "n_files": 300,
            "cache_tb": [5.0, 20.0], "egress": ["internet", "direct"]}
    kw = dict(backend="process", workers=1, cache=str(tmp_path))
    cold_drv = SweepDriver(**kw)
    cold = decide(axes, cold_drv, n_seeds=2, max_rounds=2)
    assert cold_drv.lanes_simulated > 0
    assert cold.stats["lanes_simulated"] == cold_drv.lanes_simulated
    warm_drv = SweepDriver(**kw)
    warm = decide(axes, warm_drv, n_seeds=2, max_rounds=2)
    assert warm_drv.lanes_simulated == 0 and warm_drv.configs_run == 0
    assert warm.stats["lanes_simulated"] == 0
    assert warm.stats["configs_run"] == 0
    assert warm.stats["cache_hits"] == warm_drv.cache_hits > 0
    assert warm.stats["cache"]["corrupt"] == 0
    cold_doc, warm_doc = cold.to_json_dict(), warm.to_json_dict()
    for section in ("baseline", "chosen", "frontier", "displaced_disk",
                    "break_even", "claim_holds"):
        assert warm_doc[section] == cold_doc[section], section


def _load_decide_cli():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "decide.py")
    spec = importlib.util.spec_from_file_location("decide_cli_cache", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_decide_cli_warm_rerun_serves_216_grid_from_cache(tmp_path):
    """ISSUE 6 acceptance: a warm re-run of the 216-config ``decide.py``
    grid simulates zero lanes and reproduces the cold decision report."""
    cli = _load_decide_cli()
    cache_dir = tmp_path / "cache"
    cold_out, warm_out = tmp_path / "cold.json", tmp_path / "warm.json"
    args = ["--days", "0.1", "--files", "1000", "--max-rounds", "2",
            "--quiet", "--cache-dir", str(cache_dir)]
    assert cli.main(args + ["--json", str(cold_out)]) == 0
    cold = json.loads(cold_out.read_text())
    n_grid = 4 * 3 * 9 * 2
    assert cold["stats"]["configs_run"] >= n_grid
    assert cold["stats"]["lanes_simulated"] > 0
    assert cli.main(args + ["--json", str(warm_out)]) == 0
    warm = json.loads(warm_out.read_text())
    assert warm["stats"]["lanes_simulated"] == 0
    assert warm["stats"]["configs_run"] == 0
    assert warm["stats"]["cache_hits"] >= n_grid
    for section in ("baseline", "chosen", "frontier", "displaced_disk",
                    "break_even", "claim_holds"):
        assert warm[section] == cold[section], section


def test_as_cache_coercions(tmp_path):
    cache = as_cache(str(tmp_path))
    assert isinstance(cache, ResultCache)
    assert as_cache(cache) is cache
    assert as_cache(None) is None
    assert isinstance(as_cache(LocalDirBackend(str(tmp_path))), ResultCache)
