"""Pallas kernel allclose sweeps vs. pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.carousel_update.ops import carousel_tick
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba_scan.ops import mamba_scan


@pytest.mark.parametrize("n,m", [(64, 3), (1000, 17), (2049, 33)])
@pytest.mark.parametrize("dt", [1.0, 10.0])
def test_carousel_tick_shapes(n, m, dt):
    rng = np.random.default_rng(n + m)
    link_id = jnp.asarray(rng.integers(0, m, n), jnp.int32)
    active = jnp.asarray(rng.random(n) < 0.6)
    total = jnp.asarray(rng.exponential(1e9, n).astype(np.float32) + 1e6)
    done = jnp.asarray(rng.random(n).astype(np.float32)) * total
    bw = jnp.asarray(rng.uniform(1e6, 1e8, m).astype(np.float32))
    mode = jnp.asarray(rng.integers(0, 2, m), jnp.int32)
    k = carousel_tick(link_id, active, done, total, bw, mode, dt,
                      tick_impl="pallas_interpret")
    r = carousel_tick(link_id, active, done, total, bw, mode, dt,
                      tick_impl="jnp")
    np.testing.assert_allclose(k[0], r[0], rtol=1e-5)
    assert bool((k[1] == r[1]).all())
    np.testing.assert_allclose(k[2], r[2], rtol=1e-6)


def test_carousel_tick_scalar_semantics():
    """Kernel math matches the Python event engine's per-transfer rate."""
    link_id = jnp.asarray([0, 0, 1], jnp.int32)
    active = jnp.asarray([True, True, True])
    done = jnp.zeros(3, jnp.float32)
    total = jnp.asarray([100.0, 100.0, 100.0])
    bw = jnp.asarray([10.0, 8.0], jnp.float32)
    mode = jnp.asarray([0, 1], jnp.int32)  # link0 shared, link1 throughput
    nd, comp, counts = carousel_tick(link_id, active, done, total, bw, mode,
                                     2.0, tick_impl="pallas_interpret")
    # link0 shared: 10/2 x 2 s = 10 bytes each; link1: 8 x 2 = 16
    np.testing.assert_allclose(np.asarray(nd), [10.0, 10.0, 16.0])
    assert not bool(comp.any())


# ---------------------------------------------------------------------------
# tick_impl registry (ISSUE 7): backend-aware "auto" resolution
# ---------------------------------------------------------------------------

def test_tick_impl_auto_resolution(monkeypatch):
    """"auto" is the jnp program on every platform (compiled Pallas is
    refused by Mosaic at the paper's shapes and has won no chip cell) and
    never silently interpret mode; the bare-kernel interpret default
    still follows the platform."""
    from repro.kernels import registry

    for platform in ("tpu", "gpu"):
        monkeypatch.setattr(registry, "_platform", lambda p=platform: p)
        assert registry.on_accelerator()
        assert registry.default_tick_impl() == "jnp"
        impl = registry.resolve_tick_impl("auto")
        assert impl.name == "jnp"
        assert not impl.use_kernel and not impl.interpret
        assert registry.default_interpret() is False

    monkeypatch.setattr(registry, "_platform", lambda: "cpu")
    assert not registry.on_accelerator()
    assert registry.default_tick_impl() == "jnp"
    impl = registry.resolve_tick_impl("auto")
    assert impl.name == "jnp" and not impl.use_kernel
    assert registry.default_interpret() is True
    # None means "auto"; a resolved TickImpl passes through unchanged
    assert registry.resolve_tick_impl(None).name == "jnp"
    assert registry.resolve_tick_impl(impl) is impl


def test_tick_impl_concrete_names_platform_independent(monkeypatch):
    """Concrete names never consult the backend (resolution is jax-free)."""
    from repro.kernels import registry

    def boom():
        raise AssertionError("concrete names must not probe the platform")

    monkeypatch.setattr(registry, "_platform", boom)
    for name in ("jnp", "pallas", "pallas_interpret"):
        assert registry.resolve_tick_impl(name).name == name


def test_tick_impl_unknown_name_rejected():
    from repro.kernels.registry import TICK_IMPL_CHOICES, resolve_tick_impl

    with pytest.raises(ValueError, match="tick_impl"):
        resolve_tick_impl("cuda")
    assert TICK_IMPL_CHOICES[0] == "auto"


def test_tick_impl_boolean_rejected_with_upgrade_pointer(monkeypatch):
    """A bool in the tick_impl slot (a legacy positional use_pallas
    call) gets a pointer at the removed flag and the tick_impl= upgrade
    path, not a bare KeyError — and the rejection never probes the
    platform (stays jax-free)."""
    from repro.kernels import registry

    def boom():
        raise AssertionError("boolean rejection must not probe the "
                             "platform")

    monkeypatch.setattr(registry, "_platform", boom)
    for legacy in (True, False):
        with pytest.raises(ValueError, match="use_pallas"):
            registry.resolve_tick_impl(legacy)
    assert not hasattr(registry, "tick_impl_from_use_pallas")


def test_tick_impl_resolution_counted():
    """Every resolve lands one labeled tick_impl.resolved increment."""
    from repro.kernels.registry import resolve_tick_impl
    from repro.obs.metrics import get_registry

    reg = get_registry()
    before = reg.value("tick_impl.resolved", impl="jnp", requested="jnp")
    resolve_tick_impl("jnp")
    assert reg.value("tick_impl.resolved", impl="jnp",
                     requested="jnp") == before + 1


def test_carousel_tick_use_pallas_removed():
    """The legacy keyword is gone from carousel_tick; tick_impl= is the
    only selection axis."""
    link_id = jnp.asarray([0, 1], jnp.int32)
    active = jnp.asarray([True, True])
    done = jnp.zeros(2, jnp.float32)
    total = jnp.asarray([50.0, 50.0])
    bw = jnp.asarray([10.0, 10.0], jnp.float32)
    mode = jnp.asarray([1, 1], jnp.int32)
    with pytest.raises(TypeError, match="use_pallas"):
        carousel_tick(link_id, active, done, total, bw, mode, 1.0,
                      use_pallas=False)
    new = carousel_tick(link_id, active, done, total, bw, mode, 1.0,
                        tick_impl="jnp")
    kern = carousel_tick(link_id, active, done, total, bw, mode, 1.0,
                         tick_impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(new[0]), np.asarray(kern[0]),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# lane_tick fused kernels vs. jnp oracles (interpret mode)
# ---------------------------------------------------------------------------

def _lane_transfer_oracle(link_id, active, done, total, sizes, bw, mode,
                          dt, month_onehot):
    """The pre-fusion jnp math from repro.sim.batched (eager, op-by-op)."""
    ltype = link_id % 3
    act = active.astype(np.float32)
    S, F = link_id.shape
    counts = np.zeros((S, 3), np.float32)
    for t in range(3):
        counts[:, t] = (act * (ltype == t)).sum(axis=1)
    cnt = np.take_along_axis(counts, ltype, axis=1)
    bw_f = np.take_along_axis(bw.reshape(S, 3), ltype, axis=1)
    mode_f = np.take_along_axis(mode.reshape(S, 3).astype(np.float32),
                                ltype, axis=1)
    rate = np.where(mode_f > 0.5, bw_f, bw_f / np.maximum(cnt, 1.0))
    new_done = np.minimum(total, done + act * rate * dt)
    comp = ((new_done >= total) & (act > 0.5)).astype(np.float32)
    comp_sz = sizes * comp
    tape = (comp_sz * (ltype == 0)).sum(axis=1)
    recall = (comp_sz * (ltype == 1)).sum(axis=1)
    mig = (comp_sz * (ltype == 2)).sum(axis=1)
    egress = month_onehot * recall.sum()
    cls_b = month_onehot * (comp * (ltype == 1)).sum()
    cls_a = month_onehot * (comp * (ltype == 2)).sum()
    return new_done, comp, tape, recall, mig, egress, cls_a, cls_b


def _lane_transfer_inputs(S=3, F=37, seed=0):
    rng = np.random.default_rng(seed)
    site = np.repeat(np.arange(S)[:, None], F, axis=1)
    link_id = (3 * site + rng.integers(0, 3, (S, F))).astype(np.int32)
    active = rng.random((S, F)) < 0.5
    total = (rng.exponential(1e8, (S, F)) + 1e3).astype(np.float32)
    done = (rng.random((S, F)).astype(np.float32)) * total
    sizes = total.copy()
    bw = rng.uniform(1e5, 1e7, 3 * S).astype(np.float32)
    mode = rng.integers(0, 2, 3 * S).astype(np.int32)
    month_onehot = np.zeros(4, np.float32)
    month_onehot[1] = 1.0
    return link_id, active, done, total, sizes, bw, mode, month_onehot


def test_lane_transfer_tick_matches_oracle():
    from repro.kernels import lane_tick

    (link_id, active, done, total, sizes, bw, mode,
     month_onehot) = _lane_transfer_inputs()
    dt = 50.0
    out = lane_tick.transfer_tick(
        jnp.asarray(link_id), jnp.asarray(active), jnp.asarray(done),
        jnp.asarray(total), jnp.asarray(sizes), jnp.asarray(bw),
        jnp.asarray(mode), dt, jnp.asarray(month_onehot), interpret=True)
    ref = _lane_transfer_oracle(link_id, active, done, total, sizes,
                                bw, mode, dt, month_onehot)
    # new_done can differ by FMA-fusion ulps between traces; the
    # completion mask and the billing classifications must agree exactly
    np.testing.assert_allclose(np.asarray(out[0]), ref[0], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[1]), ref[1])
    for got, want in zip(out[2:], ref[2:]):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_lane_gcs_admit_matches_global_cumsum_oracle():
    from repro.kernels import lane_tick
    from repro.sim.batched import _gcs_first_fit

    rng = np.random.default_rng(7)
    S, F = 4, 33
    want = rng.random((S, F)) < 0.4
    sizes = rng.uniform(1e6, 1e9, (S, F)).astype(np.float32)
    used0, limit = np.float32(2e9), np.float32(2e10)
    dt, month_onehot = 60.0, np.asarray([0.0, 1.0, 0.0], np.float32)

    # oracle: the first-fit passes of a global cumsum over the site-major
    # flattened candidate vector (the jnp program's ``_gcs_first_fit``)
    admitted = np.zeros((S, F), bool)
    used = used0
    passes = 0
    while True:
        rem = want & ~admitted & (sizes <= limit - used)
        if not rem.any():
            break
        csum = np.cumsum((sizes * rem).ravel()).reshape(S, F)
        new = rem & (csum <= limit - used)
        admitted |= new
        used = used + (sizes * new).sum(dtype=np.float64).astype(np.float32)
        passes += 1
    assert passes > 1 and (want & ~admitted).any()  # the quota binds

    adm, used_k, gbsec, passes_k = lane_tick.gcs_admit(
        jnp.asarray(want), jnp.asarray(sizes), used0, limit, dt,
        jnp.asarray(month_onehot), _gcs_first_fit, interpret=True)
    np.testing.assert_array_equal(np.asarray(adm), admitted)
    assert int(passes_k) == passes
    np.testing.assert_allclose(float(used_k), used, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gbsec), month_onehot * (used / 1e9 * dt), rtol=1e-5)


@pytest.mark.parametrize("fifo", [False, True])
def test_lane_window_admit_bitwise(fifo):
    from repro.kernels import lane_tick

    rng = np.random.default_rng(13 + fifo)
    S, C = 5, 6
    live = rng.random((S, C)) < 0.7
    size = rng.uniform(1e6, 5e9, (S, C)).astype(np.float32)
    used = rng.uniform(0, 1e10, S).astype(np.float32)
    limit = np.full(S, 1e10, np.float32)

    # oracle: the jnp prefix recurrence from repro.sim.batched, verbatim
    extra = np.zeros(S, np.float32)
    blocked = np.zeros(S, bool)
    adm_ref = np.zeros((S, C), np.float32)
    for k in range(C):
        fit = used + extra + size[:, k] <= limit
        if fifo:
            adm = live[:, k] & fit & ~blocked
            blocked |= live[:, k] & ~fit
        else:
            adm = live[:, k] & fit
        adm_ref[:, k] = adm
        extra = extra + np.where(adm, size[:, k], 0.0).astype(np.float32)

    adm, extra_k = lane_tick.window_admit(
        jnp.asarray(live), jnp.asarray(size), jnp.asarray(used),
        jnp.asarray(limit), fifo=fifo, interpret=True)
    np.testing.assert_array_equal(np.asarray(adm), adm_ref)
    np.testing.assert_array_equal(np.asarray(extra_k), extra)


def test_lane_kernels_vmap_lane_blocking():
    """The wrappers are written per-lane and vmap-ed by the sweep engine:
    the batch axis becomes a leading grid dimension and per-lane results
    match per-lane calls."""
    from repro.kernels import lane_tick

    L = 3
    per_lane = [_lane_transfer_inputs(seed=s) for s in range(L)]
    stacked = [jnp.asarray(np.stack([p[i] for p in per_lane]))
               for i in range(8)]
    dt = jnp.full((L,), 25.0, jnp.float32)
    batched = jax.vmap(
        lambda a, b, c, d, e, f, g, t, h: lane_tick.transfer_tick(
            a, b > 0.5, c, d, e, f, g, t, h, interpret=True))(
        stacked[0], stacked[1].astype(jnp.float32), stacked[2], stacked[3],
        stacked[4], stacked[5], stacked[6], dt, stacked[7])
    for lane, p in enumerate(per_lane):
        single = lane_tick.transfer_tick(
            jnp.asarray(p[0]), jnp.asarray(p[1]), jnp.asarray(p[2]),
            jnp.asarray(p[3]), jnp.asarray(p[4]), jnp.asarray(p[5]),
            jnp.asarray(p[6]), 25.0, jnp.asarray(p[7]), interpret=True)
        for got, want in zip(batched, single):
            np.testing.assert_allclose(np.asarray(got[lane]),
                                       np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("B,nh,nkv,T,hd", [
    (1, 2, 1, 64, 32),
    (2, 4, 2, 200, 64),
    (1, 8, 8, 256, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_sweep(B, nh, nkv, T, hd, dtype, window):
    rng = np.random.default_rng(T + hd)
    q = jnp.asarray(rng.normal(size=(B, nh, T, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(B, nkv, T, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(B, nkv, T, hd)), dtype)
    out_k = flash_attention(q, k, v, causal=True, window=window,
                            use_pallas=True)
    out_r = flash_attention(q, k, v, causal=True, window=window,
                            use_pallas=False)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("B,T,D,N", [
    (1, 64, 128, 8),
    (2, 300, 130, 16),   # unaligned: exercises padding
    (1, 512, 256, 16),
])
def test_mamba_scan_sweep(B, T, D, N):
    rng = np.random.default_rng(T + D)
    dA = jnp.asarray(np.exp(-rng.random((B, T, D, N))).astype(np.float32))
    dBu = jnp.asarray(rng.normal(size=(B, T, D, N)).astype(np.float32) * 0.1)
    C = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    yk = mamba_scan(dA, dBu, C, use_pallas=True)
    yr = mamba_scan(dA, dBu, C, use_pallas=False)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)


def test_mamba_scan_carry_across_chunks():
    """State must persist across time-chunk grid steps (scratch carry)."""
    B, T, D, N = 1, 512, 128, 4  # T spans 2 chunks of 256
    dA = jnp.ones((B, T, D, N), jnp.float32) * 0.999
    dBu = jnp.ones((B, T, D, N), jnp.float32) * 0.01
    C = jnp.ones((B, T, N), jnp.float32)
    y = mamba_scan(dA, dBu, C, use_pallas=True)
    yr = mamba_scan(dA, dBu, C, use_pallas=False)
    # monotonically increasing accumulation; chunk boundary must not reset
    assert float(y[0, 256, 0]) > float(y[0, 255, 0]) > float(y[0, 0, 0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4)


def test_model_ssm_block_runs_finite():
    """Smoke: models.ssm's block runs end-to-end and stays finite (kernel
    vs. reference parity is covered by the mamba_scan tests above)."""
    from repro.configs import get_smoke_config
    from repro.models.ssm import init_ssm, ssm_block
    cfg = get_smoke_config("falcon_mamba_7b")
    params = init_ssm(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          dtype=cfg.dtype)

    ref_out = ssm_block(params, cfg, x)
    assert bool(jnp.isfinite(ref_out.astype(jnp.float32)).all())
