"""MoE dispatch/combine correctness, including the shard-local EP path."""

import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import moe_combine, moe_dispatch, router_topk


def test_dispatch_combine_roundtrip_identity():
    """With ample capacity and identity 'experts', combine(dispatch(x))
    reproduces gate-weighted copies of x."""
    rng = np.random.default_rng(0)
    T, d, E, k = 32, 8, 4, 2
    x = jnp.asarray(rng.normal(size=(T, d)).astype(np.float32))
    logits = jnp.asarray(rng.normal(size=(T, E)).astype(np.float32))
    gates, idx, aux = router_topk(logits, k)
    cap = T * k  # dropless
    buf, e_sel, p_sel = moe_dispatch(x, idx, cap, E)
    out = moe_combine(buf, gates, e_sel, p_sel)
    # identity experts: out == sum_k gate_k * x = x (gates normalized)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) > 0


def test_dispatch_capacity_drops_overflow():
    T, d, E, k = 16, 4, 2, 1
    x = jnp.ones((T, d), jnp.float32)
    idx = jnp.zeros((T, k), jnp.int32)  # every token -> expert 0
    cap = 4
    buf, e_sel, p_sel = moe_dispatch(x, idx, cap, E)
    assert buf.shape == (E, cap + 1, d)
    # only `cap` tokens land in real slots; rest in the dead column
    assert float(buf[0, :cap].sum()) == cap * d
    gates = jnp.ones((T, k), jnp.float32)
    out = moe_combine(buf, gates, e_sel, p_sel)
    kept = float((out.sum(-1) > 0).sum())
    assert kept == cap


def test_router_topk_normalized_gates():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(64, 8)),
                         jnp.float32)
    gates, idx, aux = router_topk(logits, 3)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)),
                               np.ones(64), rtol=1e-5)
    assert int(idx.max()) < 8


@pytest.mark.slow
def test_local_dispatch_matches_global_multidevice():
    """Shard-local dispatch + A2A must match global dispatch (4 host
    devices, dropless capacity). Runs in a subprocess so the forced
    device count does not leak into this test session."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models.moe import init_moe, moe_layer
        from repro.parallel.ctx import sharding_ctx

        cfg = get_smoke_config("olmoe_1b_7b").replace(capacity_factor=16.0)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model),
                              dtype=cfg.dtype)
        with mesh:
            with sharding_ctx(mesh, moe_local_dispatch=False):
                ref, _ = jax.jit(lambda p, x: moe_layer(p, cfg, x))(p, x)
            with sharding_ctx(mesh, moe_local_dispatch=True):
                loc, _ = jax.jit(lambda p, x: moe_layer(p, cfg, x))(p, x)
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                    - loc.astype(jnp.float32))))
        assert err < 0.05, f"local vs global dispatch mismatch: {err}"
        print("OK", err)
    """)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              # without this jax hangs probing for non-CPU
                              # backends on machines without accelerators
                              "JAX_PLATFORMS": "cpu"})
    assert "OK" in res.stdout, res.stderr[-2000:]
