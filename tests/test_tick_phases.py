"""Two phases of the batched tick against plain numpy oracles.

``repro.sim.batched._advance_transfers`` is the carousel's transfer
advance over the ``[S, F]`` file planes, and ``_window_admit`` the
prefix recurrence that admits a per-site candidate window (this tick's
job arrivals, or the waiting queue's heads) against the disk limit.
Each is checked here on its own, from one site of 64 files up to three
sites of 2049, and vmapped over lanes as the grid program runs it.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sim.batched import _advance_transfers, _window_admit

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
