"""Three phases of the batched tick against plain numpy oracles.

``repro.sim.batched._advance_transfers`` is the carousel's transfer
advance over the ``[S, F]`` file planes, ``_window_admit`` the prefix
recurrence that admits a per-site candidate window (this tick's job
arrivals, or the waiting queue's heads) against the disk limit, and
``_apply_plans`` the scatter of both planned windows into the planes.
Each is checked here on its own, from one site of 64 files up to three
sites of 2049, and vmapped over lanes as the grid program runs it. The
whole program is held to a frozen copy of its outputs for one request
of each benchmark deployment.
"""

import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scenarios import ScenarioSpec, pack_specs
from repro.sim.batched import (
    ABSENT,
    IN_FLIGHT,
    _advance_transfers,
    _apply_plans,
    _window_admit,
    simulate_packed,
)

MiB = 2.0 ** 20
GiB = 2.0 ** 30


def _transfer_inputs(S, F, seed):
    """Transfers on each site's three links (link id ``3 * site +
    type``), about 60% of them active. Sizes are whole MiB, so every
    per-site sum of completed bytes is exact in float32."""
    rng = np.random.default_rng(seed)
    site = np.repeat(np.arange(S)[:, None], F, axis=1)
    link_id = (3 * site + rng.integers(0, 3, (S, F))).astype(np.int32)
    active = rng.random((S, F)) < 0.6
    sizes = (rng.integers(1, 1024, (S, F)) * MiB).astype(np.float32)
    done = (rng.random((S, F)) * sizes).astype(np.float32)
    bw = rng.uniform(1e7, 1e9, 3 * S).astype(np.float32)
    mode = rng.integers(0, 2, 3 * S).astype(np.int32)
    return link_id, active, done, sizes, bw, mode


def _transfer_oracle(link_id, active, done, total, sizes, bw, mode, dt):
    """One tick of the carousel, eagerly in numpy: a shared link (mode 0)
    splits its bandwidth among its active transfers, a dedicated one
    gives each its full bandwidth. Returns ``(new_done, comp, bytes)``,
    ``bytes`` the per-site completed bytes by link type."""
    ltype = link_id % 3
    act = active.astype(np.float32)
    S = link_id.shape[0]
    counts = np.zeros((S, 3), np.float32)
    for t in range(3):
        counts[:, t] = (act * (ltype == t)).sum(axis=1)
    cnt = np.take_along_axis(counts, ltype, axis=1)
    bw_f = np.take_along_axis(bw.reshape(S, 3), ltype, axis=1)
    mode_f = np.take_along_axis(mode.reshape(S, 3), ltype, axis=1)
    rate = np.where(mode_f > 0, bw_f, bw_f / np.maximum(cnt, 1.0))
    new_done = np.minimum(total, done + act * rate * np.float32(dt))
    comp = (new_done >= total) & active
    by_type = [(sizes * (comp & (ltype == t))).sum(axis=1) for t in range(3)]
    return new_done, comp, np.stack(by_type)


def _advance(link_id, active, done, total, sizes, bw, mode, dt):
    """``_advance_transfers`` with the tick's link-type inputs, and the
    per-site completed bytes the tick bills from its masks."""
    ltype = link_id % 3
    onehot = ltype[:, :, None] == jnp.arange(3, dtype=jnp.int32)
    new_done, comp, by_type = _advance_transfers(
        active, ltype, onehot, done, total, bw, mode, dt)
    return new_done, comp, jnp.stack([jnp.sum(sizes * c, axis=1)
                                      for c in by_type])


TRANSFER_CASES = list(itertools.product([(1, 64), (2, 1000), (3, 2049)],
                                        [1.0, 10.0, 60.0]))


@pytest.mark.parametrize(
    "S,F,dt", [(S, F, dt) for (S, F), dt in TRANSFER_CASES],
    ids=[f"{S}-{F}-{dt:g}" for (S, F), dt in TRANSFER_CASES])
def test_transfer_advance_matches_oracle(S, F, dt):
    link_id, active, done, sizes, bw, mode = _transfer_inputs(S, F, S * F)
    got = jax.jit(_advance)(link_id, active, done, sizes, sizes, bw, mode,
                            dt)
    want = _transfer_oracle(link_id, active, done, sizes, sizes, bw, mode,
                            dt)
    # the bytes done may differ from numpy's by a fused multiply-add's
    # rounding; the completion mask and the billed bytes are exact
    np.testing.assert_allclose(np.asarray(got[0]), want[0], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[1]), want[1])
    np.testing.assert_array_equal(np.asarray(got[2]), want[2])
    if dt == 60.0:
        assert want[1].any() and not want[1].all()


def test_transfer_advance_hand_worked():
    """Two transfers share link 0 (10 B/s); link 1 is dedicated (8 B/s):
    after 2 s the shared ones hold 10/2 x 2 = 10 bytes each, the
    dedicated one 8 x 2 = 16, and none of the 100-byte files is done."""
    link_id = jnp.asarray([[0, 0, 1]], jnp.int32)
    active = jnp.asarray([[True, True, True]])
    total = jnp.full((1, 3), 100.0, jnp.float32)
    bw = jnp.asarray([10.0, 8.0, 1.0], jnp.float32)
    mode = jnp.asarray([0, 1, 0], jnp.int32)
    new_done, comp, by_bytes = _advance(link_id, active, jnp.zeros((1, 3)),
                                        total, total, bw, mode, 2.0)
    np.testing.assert_array_equal(np.asarray(new_done), [[10.0, 10.0, 16.0]])
    assert not bool(comp.any()) and not bool(by_bytes.any())


def _window_oracle(live, size, used, limit, fifo, stale=None):
    """The window's prefix recurrence, candidate by candidate, in numpy
    float32."""
    if stale is not None:
        live = live & ~stale
    S, C = live.shape
    extra = np.zeros(S, np.float32)
    blocked = np.zeros(S, bool)
    adm_ref = np.zeros((S, C), bool)
    for k in range(C):
        fit = used + extra + size[:, k] <= limit
        adm = live[:, k] & fit
        if fifo:
            adm = adm & ~blocked
            blocked |= live[:, k] & ~fit
        adm_ref[:, k] = adm
        extra = extra + np.where(adm, size[:, k], 0.0).astype(np.float32)
    return adm_ref, extra


def _random_window(rng):
    live = rng.random((5, 6)) < 0.7
    size = rng.uniform(1e6, 5e9, (5, 6)).astype(np.float32)
    used = rng.uniform(0, 1e10, 5).astype(np.float32)
    limit = np.full(5, 1e10, np.float32)
    return live, size, used, limit, rng.random((5, 6)) < 0.2


def _gib(*rows):
    return (np.asarray(rows, np.float64) * GiB).astype(np.float32)


WINDOW_CASES = {
    "random": _random_window,
    "empty": lambda r: (np.zeros((3, 4), bool), _gib([1] * 4, [2] * 4,
                                                      [3] * 4),
                        _gib(0, 0, 0), _gib(10, 10, 10), None),
    # the disk is full: nothing goes in
    "none_fit": lambda r: (np.ones((2, 4), bool), _gib([1] * 4, [5] * 4),
                           _gib(10, 7), _gib(10, 10), None),
    # 1 + 1 + 2 GiB fill the room of 4 GiB exactly; the 1 GiB after them
    # does not fit
    "exact_fit_at_limit": lambda r: (np.ones((1, 4), bool),
                                     _gib([1, 1, 2, 1]), _gib(6), _gib(10),
                                     None),
    # a head too big for the room, small files behind it
    "big_head_then_small": lambda r: (np.ones((2, 4), bool),
                                      _gib([8, 1, 1, 1], [1, 8, 1, 1]),
                                      _gib(5, 5), _gib(10, 10), None),
    # every site has its own disk: one takes all, one none, one some
    "sites_differ": lambda r: (np.ones((3, 3), bool),
                               _gib([1, 2, 3], [1, 2, 3], [2, 2, 2]),
                               _gib(0, 10, 5), _gib(100, 10, 10), None),
}


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
@pytest.mark.parametrize("fifo", [False, True], ids=["window", "fifo"])
def test_window_admit_matches_recurrence(fifo, case):
    live, size, used, limit, stale = WINDOW_CASES[case](
        np.random.default_rng(13))
    got, extra = jax.jit(_window_admit, static_argnums=4)(
        live, size, used, limit, fifo, stale)
    want, want_extra = _window_oracle(live, size, used, limit, fifo, stale)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(extra), want_extra)
    assert (np.asarray(used + extra) <= limit).all()
    if case == "empty" or case == "none_fit":
        assert not want.any()
    if case == "exact_fit_at_limit":
        assert want.tolist() == [[True, True, True, False]]
        assert want_extra.tolist() == [4 * GiB]
    if case == "big_head_then_small":
        # passed over without FIFO; with it the head blocks its queue
        assert want[0].tolist() == [False, not fifo, not fifo, not fifo]
        assert want[1].tolist() == [True, False, not fifo, not fifo]
    if case == "sites_differ":
        assert want.sum(axis=1).tolist() == [3, 0, 2]


@pytest.mark.parametrize("phase", ["transfer", "admit"])
def test_tick_phases_vmap_per_lane(phase):
    """Vmapped over lanes, as the grid program runs the tick, each lane
    gives what a call on that lane alone gives."""
    L = 3
    if phase == "transfer":
        lanes = [_transfer_inputs(2, 300, seed) for seed in range(L)]
        dts = np.asarray([1.0, 10.0, 60.0], np.float32)

        def call(link_id, active, done, sizes, bw, mode, dt):
            return _advance(link_id, active, done, sizes, sizes, bw, mode,
                            dt)
    else:
        lanes = [_random_window(np.random.default_rng(seed))
                 for seed in range(L)]
        dts = None

        def call(live, size, used, limit, stale):
            return _window_admit(live, size, used, limit, True, stale)

    stacked = [np.stack(x) for x in zip(*lanes)]
    if dts is not None:
        stacked.append(dts)
    batched = jax.jit(jax.vmap(call))(*stacked)
    for li, lane in enumerate(lanes):
        args = list(lane) + ([dts[li]] if dts is not None else [])
        single = jax.jit(call)(*args)
        for i, (got, want) in enumerate(zip(batched, single)):
            if phase == "transfer" and i == 0:  # bytes done: see above
                np.testing.assert_allclose(np.asarray(got[li]),
                                           np.asarray(want), rtol=1e-6)
            else:
                np.testing.assert_array_equal(np.asarray(got[li]),
                                              np.asarray(want))


# ------------------------------------------------ applying the windows
#: The planes ``_apply_plans`` writes: dtype and the fields of the
#: arrivals' plan that reach them (``None``: both windows).
APPLY_PLANES = {
    "disk_state": np.int32, "wq_wait": bool, "tr_link": np.int32,
    "tr_total": np.float32, "tr_slot": bool, "tr_start": np.float32,
    "lq_ticket": np.int32, "lq_queued": bool, "wq_ticket": np.int32,
    "pend_cnt": np.int32, "pend_tail": np.float32, "fin_max": np.float32,
}
ARRIVAL_PLANES = ("wq_ticket", "pend_cnt", "pend_tail", "fin_max")


def _apply_inputs(S, F, C, W, seed):
    """Random planes and two windows, the arrivals (``C`` per site) and
    the queue heads (``W``), that name few files: every file of a site
    appears several times, in both windows."""
    rng = np.random.default_rng(seed)

    def real(shape):  # bytes and seconds, and the planes' floats
        return rng.uniform(1e3, 1e10, shape).astype(np.float32)

    def plane(dtype):
        if dtype is bool:
            return rng.random((S, F)) < 0.5
        if dtype is np.int32:
            return rng.integers(-50, 50, (S, F)).astype(np.int32)
        return real((S, F))

    def window(n):
        fid = rng.integers(0, F, (S, n)).astype(np.int32)
        fire = rng.random((S, n)) < 0.6
        direct = fire & (rng.random((S, n)) < 0.5)
        return dict(
            site=np.repeat(np.arange(S, dtype=np.int32)[:, None], n, 1),
            fid=fid, fire=fire, stale=rng.random((S, n)) < 0.3,
            m_vec=rng.integers(0, 3 * S, (S, n)).astype(np.int32),
            direct=direct, queued=fire & ~direct,
            tstart=np.where(direct, real((S, n)), np.inf),
            lq_val=rng.integers(0, 1000, (S, n)).astype(np.int32))

    st = {name: plane(dtype) for name, dtype in APPLY_PLANES.items()}
    st["tr_total"][rng.random((S, F)) < 0.5] = np.inf  # no transfer yet
    sizes = real((S, F))
    arrivals, heads = window(C), window(W)
    pend_add = rng.random((S, C)) < 0.5
    arrivals.update(
        to_wait=rng.random((S, C)) < 0.4,
        wq_val=rng.integers(0, 1000, (S, C)).astype(np.int32),
        pend_add=pend_add,
        tail=real((S, C)),
        fin_val=np.where(pend_add, -np.inf, real((S, C))))
    return st, [arrivals, heads], sizes


def _apply_oracle(st, plans, sizes, arrivals):
    """The windows' rows one by one, in window order, in numpy: deltas
    from the pre-scatter planes, ``wq_wait``'s ``min`` over every row
    before its ``max`` over the arrivals'."""
    out = {name: st[name].copy() for name in APPLY_PLANES
           if arrivals is not None or name not in ARRIVAL_PLANES}

    def rows(windows):
        for p in windows:
            for s, c in np.ndindex(p["fid"].shape):
                yield p, (s, c), (p["site"][s, c], p["fid"][s, c])

    for p, k, at in rows(plans):
        if p["fire"][k]:
            out["disk_state"][at] += IN_FLIGHT - ABSENT
            out["tr_link"][at] += p["m_vec"][k] - st["tr_link"][at]
            out["tr_total"][at] = min(out["tr_total"][at], sizes[at])
        out["wq_wait"][at] &= ~(p["fire"][k] | p["stale"][k])
        out["tr_slot"][at] |= p["direct"][k]
        out["tr_start"][at] = min(out["tr_start"][at], p["tstart"][k])
        if p["queued"][k]:
            out["lq_ticket"][at] += p["lq_val"][k] - st["lq_ticket"][at]
        out["lq_queued"][at] |= p["queued"][k]
    if arrivals is not None:
        for p, k, at in rows([arrivals]):
            out["wq_wait"][at] |= p["to_wait"][k]
            if p["to_wait"][k]:
                out["wq_ticket"][at] += p["wq_val"][k] - st["wq_ticket"][at]
            if p["pend_add"][k]:
                out["pend_cnt"][at] += 1
                out["pend_tail"][at] = max(out["pend_tail"][at],
                                           p["tail"][k])
            else:
                out["pend_tail"][at] = max(out["pend_tail"][at], 0.0)
            out["fin_max"][at] = max(out["fin_max"][at], p["fin_val"][k])
    return out


APPLY_CASES = {
    # three files a site: every file is named by several rows of both
    # windows
    "crowded": (2, 3, 8, 4, 5),
    "crowded_no_arrivals": (2, 3, 0, 4, 2),
    "spread": (2, 1000, 8, 4, 3),
    "three_sites": (3, 5, 16, 4, 4),
}


@pytest.mark.parametrize("case", sorted(APPLY_CASES))
def test_apply_plans_matches_row_by_row_oracle(case):
    S, F, C, W, seed = APPLY_CASES[case]
    st, plans, sizes = _apply_inputs(S, F, max(C, 1), W, seed)
    if C == 0:
        plans = plans[1:]
    arrivals = plans[0] if C else None
    got = jax.jit(_apply_plans)(st, plans, sizes, arrivals)
    want = _apply_oracle(st, plans, sizes, arrivals)
    assert sorted(got) == sorted(want)
    for name, plane in want.items():
        assert got[name].dtype == plane.dtype, name
        np.testing.assert_array_equal(np.asarray(got[name]), plane,
                                      err_msg=name)
    if case == "crowded":
        # the windows overlap, and the updates do change the planes
        fids = [set(p["fid"][0]) for p in plans]
        assert fids[0] & fids[1]
        assert all((want[n] != st[n]).any() for n in want)


def test_apply_plans_vmap_per_lane():
    """Vmapped over lanes, as the grid program runs the tick, each lane
    gives what a call on that lane alone gives."""
    lanes = [_apply_inputs(2, 7, 8, 4, seed) for seed in range(3)]

    def call(st, plans, sizes):
        return _apply_plans(st, plans, sizes, plans[0])

    stacked = jax.tree.map(lambda *x: np.stack(x), *lanes)
    batched = jax.jit(jax.vmap(call))(*stacked)
    for li, lane in enumerate(lanes):
        single = jax.jit(call)(*lane)
        for name in APPLY_PLANES:
            np.testing.assert_array_equal(np.asarray(batched[name][li]),
                                          np.asarray(single[name]),
                                          err_msg=name)


# -------------------------------------- the whole program, frozen outputs
#: One request of each benchmark deployment (``benchmarks/chip/configs``)
#: at 20,000 files per site: two lanes, 0.25 days at 60 s.
FROZEN_CELLS = {
    "cfgIII": dict(base="III", cache_tb=10.0, gcs_limit_tb=None),
    "cfgII": dict(base="II", cache_tb=10.0, gcs_limit_tb=0.0),
    "cfgIII-gcs20": dict(base="III", cache_tb=10.0, gcs_limit_tb=20.0),
}
FROZEN_SEEDS = (5, 2 ** 62 + 9)
FROZEN_PATH = os.path.join(os.path.dirname(__file__), "testdata",
                           "tick_f20k_outputs.npz")


def _frozen_request(cell):
    specs = [ScenarioSpec(days=0.25, n_files=20_000, seed=s,
                          **FROZEN_CELLS[cell]) for s in FROZEN_SEEDS]
    out = simulate_packed(pack_specs(specs, tick=60.0), record_series=True)
    return {f"{cell}.{k}": np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("cell", sorted(FROZEN_CELLS))
def test_program_outputs_equal_frozen_copy(cell):
    """Every lane output of the request, the per-tick series included,
    is bitwise the frozen copy written by the program before its
    deferred scatters addressed the planes by ``(site, fid)``."""
    got = _frozen_request(cell)
    with np.load(FROZEN_PATH) as frozen:
        want = {k: frozen[k] for k in frozen.files
                if k.startswith(cell + ".")}
    assert sorted(got) == sorted(want)
    for key, value in want.items():
        assert got[key].dtype == value.dtype, key
        np.testing.assert_array_equal(got[key], value, err_msg=key)
    assert (got[f"{cell}.ser_gcs"] > 0).any() == (cell != "cfgII")
