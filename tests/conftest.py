import os
import sys

import pytest

# Tests see the single real CPU device (the dry-run sets its own 512-device
# flag in a subprocess); keep memory modest and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(monkeypatch):
    """Tests never turn JAX's persistent compile cache on. The CLIs that
    tests drive in-process call ``use_compile_cache``, which would switch
    it on for the rest of the worker and write into the checkout."""
    from repro.sim import compile_cache

    monkeypatch.setattr(compile_cache, "use_compile_cache",
                        lambda: compile_cache.DEFAULT_CACHE_DIR)
