"""Pallas kernels: the fused lane-blocked batched-sweep tick.

The site-vectorized tick program (``repro.sim.batched``) spends its time
in three dense pieces; each becomes one Pallas kernel here, selected via
the ``tick_impl`` registry (``repro.kernels.registry``):

- ``transfer_kernel``: the carousel transfer advance (per-link active
  counts, bandwidth-share rates, progress integration, completion) fused
  with the completion *billing* that ``repro.sim.batched`` previously
  applied as separate jnp reductions — per-site tape/recall/migration
  byte totals plus the month-bucketed egress volume and class A/B
  operation counts. Grid is one step per site: a site's three links are
  private to its row (link id = 3*site + type), so per-link counts never
  cross blocks and the whole tick is block-local one-hot matmuls
  (``carousel_update`` design notes: gathers become MXU ``dot``s).
- ``gcs_admit_pass_kernel``: one pass of the shared-GCS first-fit
  admission gate (``repro.sim.batched._gcs_first_fit``): candidates
  larger than the room left are dropped, then the prefix of the rest
  whose cumsum fits is admitted. Each pass is one ``pallas_call`` over
  the sequential site grid, the running cumsum carried across site
  blocks in a small VMEM-resident carry ref and the previous pass's
  admitted mask re-entering as a true (aliased) input, fused with the
  end-of-tick GB-second storage integration; ``_gcs_first_fit``'s own
  ``lax.while_loop`` runs it as each pass while a fitting candidate is
  left, so the rule and the loop live in one place. (Passes cannot
  share one grid: compiled Pallas only preserves an output window's
  VMEM contents across *consecutive* grid steps on the same block, and
  a ``(passes, S)`` grid revisits each site block non-consecutively.) The
  blocked cumsum reassociates the float pass totals, so admission can
  differ from the jnp program by capacity-boundary ties — statistical
  (Table-2) parity, not bitwise; see ``docs/simulation.md``.
- ``window_kernel``: the [S, K] job-arrival and [S, W] waiting-queue
  admission windows — C-step prefix recurrences (later candidates see
  earlier reservations; the wait queue additionally head-blocks) over
  all sites at once. Identical operation order to the jnp loops, so this
  kernel is bitwise-equal to the oracle.

Lane blocking: the wrappers are written for one lane ([S, F] planes) and
are ``jax.vmap``-ed by the caller — Pallas turns the batch axis into an
extra leading grid dimension, so a packed sweep grid executes as
lane x site blocks from one ``pallas_call``.

Booleans cross the kernel boundary as f32 0/1 masks (TPU-friendly; the
callers threshold at 0.5). Scalars ride as shape-(1,) VMEM inputs, the
month selector as a precomputed one-hot over the month axis so billing
accumulates with a multiply instead of a scatter.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.registry import default_interpret

#: File-axis tile: [S, F] planes are zero-padded to a multiple of this
#: (8 sublanes x 128 lanes = one f32 TPU tile per 8 sites).
F_BLOCK = 128


def _pad_f(arr, fp: int, value=0):
    """Pad the trailing (file) axis of a [S, F] plane to ``fp`` columns."""
    f = arr.shape[-1]
    if f == fp:
        return arr
    return jnp.pad(arr, ((0, 0), (0, fp - f)), constant_values=value)


def _onehot3(ltype: jnp.ndarray) -> jnp.ndarray:
    """[F] int32 link-type -> [F, 3] f32 one-hot (MXU operand)."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (ltype.shape[0], 3), 1)
    return (ltype[:, None] == cols).astype(jnp.float32)


# ---------------------------------------------------------------------------
# transfer advance + completion billing
# ---------------------------------------------------------------------------

def transfer_kernel(link_ref, act_ref, done_ref, total_ref, sizes_ref,
                    bw_ref, mode_ref, dt_ref, month_ref,
                    new_done_ref, comp_ref, tape_ref, recall_ref, mig_ref,
                    egress_ref, cls_a_ref, cls_b_ref):
    """One site's transfer tick + billing. Grid: (S,); blocks (1, F).

    The month-bucketed accumulators (egress bytes, class A/B counts) map
    every site to the same [n_months] block and accumulate across the
    sequential site grid (read-modify-write after an ``i == 0`` init,
    the ``carousel_update.count_kernel`` pattern).
    """
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        egress_ref[...] = jnp.zeros_like(egress_ref)
        cls_a_ref[...] = jnp.zeros_like(cls_a_ref)
        cls_b_ref[...] = jnp.zeros_like(cls_b_ref)

    ltype = link_ref[0, :] % 3  # 0 tape->disk, 1 gcs->disk, 2 disk->gcs
    onehot = _onehot3(ltype)    # [F, 3]
    act = act_ref[0, :]
    # per-link-type active counts, then broadcast back per transfer — two
    # MXU matmuls instead of a segment-sum + gather
    counts3 = jnp.dot(act[None, :], onehot,
                      preferred_element_type=jnp.float32)  # [1, 3]
    cnt = jnp.dot(onehot, counts3.reshape(3, 1),
                  preferred_element_type=jnp.float32)[:, 0]
    bw = jnp.dot(onehot, bw_ref[...].reshape(3, 1),
                 preferred_element_type=jnp.float32)[:, 0]
    mode = jnp.dot(onehot, mode_ref[...].reshape(3, 1),
                   preferred_element_type=jnp.float32)[:, 0]
    shared = bw / jnp.maximum(cnt, 1.0)
    rate = jnp.where(mode > 0.5, bw, shared)
    total = total_ref[0, :]
    new_done = jnp.minimum(total, done_ref[0, :] + act * rate * dt_ref[0])
    comp = ((new_done >= total) & (act > 0.5)).astype(jnp.float32)
    new_done_ref[0, :] = new_done
    comp_ref[0, :] = comp

    # completion billing, classified by link type
    sz = sizes_ref[0, :]
    comp_sz = sz * comp
    tape_ref[0] = jnp.sum(comp_sz * onehot[:, 0])
    recall_b = jnp.sum(comp_sz * onehot[:, 1])
    recall_ref[0] = recall_b
    mig_ref[0] = jnp.sum(comp_sz * onehot[:, 2])
    month = month_ref[...]
    egress_ref[...] += month * recall_b
    cls_b_ref[...] += month * jnp.sum(comp * onehot[:, 1])
    cls_a_ref[...] += month * jnp.sum(comp * onehot[:, 2])


def transfer_tick(link_id, active, done, total, sizes, bw, mode, dt,
                  month_onehot, interpret: Optional[bool] = None):
    """One fused transfer tick over a lane's [S, F] transfer planes.

    link_id: [S, F] i32 (3*site + type); active: [S, F] bool;
    done/total/sizes: [S, F] f32; bw: [3*S] f32; mode: [3*S] i32/f32;
    dt: f32 scalar; month_onehot: [n_months] f32 selector.

    Returns ``(new_done [S,F] f32, completed [S,F] f32 mask,
    tape_bytes [S], recall_bytes [S], migrate_bytes [S],
    egress_mo [n_months], cls_a_mo [n_months], cls_b_mo [n_months])``.
    """
    if interpret is None:
        interpret = default_interpret()
    S, F = link_id.shape
    n_months = month_onehot.shape[0]
    fp = F + (-F) % F_BLOCK
    args = (
        _pad_f(link_id, fp),
        _pad_f(active.astype(jnp.float32), fp),
        _pad_f(done, fp),
        _pad_f(total, fp, value=jnp.inf),
        _pad_f(sizes, fp),
        bw.reshape(S, 3),
        mode.astype(jnp.float32).reshape(S, 3),
        jnp.reshape(dt, (1,)).astype(jnp.float32),
        month_onehot.astype(jnp.float32),
    )
    row = pl.BlockSpec((1, fp), lambda s: (s, 0))
    site = pl.BlockSpec((1,), lambda s: (s,))
    months = pl.BlockSpec((n_months,), lambda s: (0,))
    out = pl.pallas_call(
        transfer_kernel,
        grid=(S,),
        in_specs=[row, row, row, row, row,
                  pl.BlockSpec((1, 3), lambda s: (s, 0)),
                  pl.BlockSpec((1, 3), lambda s: (s, 0)),
                  pl.BlockSpec((1,), lambda s: (0,)),
                  months],
        out_specs=[row, row, site, site, site, months, months, months],
        out_shape=[
            jax.ShapeDtypeStruct((S, fp), jnp.float32),
            jax.ShapeDtypeStruct((S, fp), jnp.float32),
            jax.ShapeDtypeStruct((S,), jnp.float32),
            jax.ShapeDtypeStruct((S,), jnp.float32),
            jax.ShapeDtypeStruct((S,), jnp.float32),
            jax.ShapeDtypeStruct((n_months,), jnp.float32),
            jax.ShapeDtypeStruct((n_months,), jnp.float32),
            jax.ShapeDtypeStruct((n_months,), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    new_done, comp = out[0][:, :F], out[1][:, :F]
    return (new_done, comp) + tuple(out[2:])


# ---------------------------------------------------------------------------
# shared-GCS prefix-sum admission
# ---------------------------------------------------------------------------

def gcs_admit_pass_kernel(want_ref, sizes_ref, adm_in_ref, used0_ref,
                          limit_ref, dt_ref, month_ref,
                          adm_ref, used_ref, gbsec_ref, carry_ref):
    """One first-fit pass. Grid: (S,) sequential.

    ``adm_in_ref`` is the previous pass's admitted mask entering as a
    true input (buffer-aliased onto ``adm_ref``): each site block is
    visited exactly once per call, so no output window is revisited
    after intervening blocks — compiled Pallas only guarantees VMEM
    persistence across *consecutive* grid steps on the same block.
    ``used0_ref`` is the pass-start occupancy, frozen for the whole pass
    exactly like the jnp program's, and sets the room; candidates larger
    than the room are left out of the pass. ``carry_ref`` is a 2-slot
    accumulator (every step maps to the same block, hence persistent;
    written as an output the caller discards): [0] bytes admitted within
    this pass, [1] running candidate cumsum carried across site blocks
    (the blocked image of the jnp global cumsum)."""
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _pass_init():
        carry_ref[0] = 0.0
        carry_ref[1] = 0.0

    adm_prev = adm_in_ref[...]
    sz = sizes_ref[...]
    room = limit_ref[0] - used0_ref[0]
    rem = (want_ref[...] > 0.5) & ~(adm_prev > 0.5) & (sz <= room)
    remf = rem.astype(jnp.float32)
    csum = jnp.cumsum(sz * remf, axis=-1) + carry_ref[1]
    new = rem & (csum <= room)
    newf = new.astype(jnp.float32)
    adm_ref[...] = jnp.maximum(adm_prev, newf)
    carry_ref[0] += jnp.sum(sz * newf)
    carry_ref[1] += jnp.sum(sz * remf)
    used = used0_ref[0] + carry_ref[0]
    used_ref[0] = used
    # end-of-tick storage integration (last grid step's write wins, with
    # the pass-end occupancy; the caller keeps the final pass's value)
    gbsec_ref[...] = month_ref[...] * (used / 1e9 * dt_ref[0])


def gcs_admit(want, sizes, gcs_used, gcs_limit, dt, month_onehot,
              first_fit, interpret: Optional[bool] = None):
    """First-fit shared-capacity admission over a lane's [S, F] candidate
    plane: ``first_fit`` (``repro.sim.batched._gcs_first_fit``, which
    holds the gate's rule and its loop) run with a Pallas call as each
    pass.

    want: [S, F] bool migration candidates; sizes: [S, F] f32 bytes;
    gcs_used/gcs_limit: f32 scalars; dt: f32 scalar tick length;
    month_onehot: [n_months] f32.

    Returns ``(admitted [S, F] bool, gcs_used' f32 scalar,
    gbsec_mo_delta [n_months], passes int32)`` — the third output is the
    ``gcs_used'/1e9*dt`` month-bucketed GB-second integration, fused
    into the last pass (computed here when no pass runs).

    Each pass is one ``pallas_call`` (see ``gcs_admit_pass_kernel``) over
    the loop's candidates that fit the room; the admitted mask and the
    pass-start occupancy flow between passes as loop values, the mask
    donated back in via ``input_output_aliases``.
    """
    if interpret is None:
        interpret = default_interpret()
    S, F = want.shape
    n_months = month_onehot.shape[0]
    fp = F + (-F) % F_BLOCK
    wantp = _pad_f(want, fp)
    sizesf = _pad_f(sizes, fp)
    limit = jnp.reshape(gcs_limit, (1,)).astype(jnp.float32)
    dtv = jnp.reshape(dt, (1,)).astype(jnp.float32)
    monthf = month_onehot.astype(jnp.float32)
    row = pl.BlockSpec((1, fp), lambda s: (s, 0))
    one = pl.BlockSpec((1,), lambda s: (0,))
    months = pl.BlockSpec((n_months,), lambda s: (0,))
    admit_pass = pl.pallas_call(
        gcs_admit_pass_kernel,
        grid=(S,),
        in_specs=[row, row, row, one, one, one, months],
        out_specs=[row, one, months, pl.BlockSpec((2,), lambda s: (0,))],
        out_shape=[
            jax.ShapeDtypeStruct((S, fp), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
            jax.ShapeDtypeStruct((n_months,), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.float32),
        ],
        input_output_aliases={2: 0},
        interpret=interpret,
    )

    def gate_pass(admitted, used, rem, _gbsec):
        admitted, used, gbsec, _carry = admit_pass(
            rem.astype(jnp.float32), sizesf, admitted.astype(jnp.float32),
            jnp.reshape(used, (1,)), limit, dtv, monthf)
        return admitted > 0.5, used[0], gbsec

    gbsec = monthf * (gcs_used / 1e9 * dtv[0])  # the value with no pass
    admitted, used, passes, gbsec = first_fit(wantp, sizesf, gcs_used,
                                              gcs_limit, gate_pass, gbsec)
    return admitted[:, :F], used, gbsec, passes


# ---------------------------------------------------------------------------
# candidate-window prefix recurrences
# ---------------------------------------------------------------------------

def window_kernel(live_ref, size_ref, used_ref, limit_ref,
                  adm_ref, extra_ref, *, n_cols: int, fifo: bool):
    """All sites' C-step admission recurrence in one block ([S, C] refs;
    the window is tiny, so C unrolls statically). ``fifo`` adds the
    wait-queue head-blocking carry; operation order matches the jnp
    loops in ``repro.sim.batched`` exactly (bitwise oracle parity)."""
    used = used_ref[:, 0]
    limit = limit_ref[:, 0]
    extra = jnp.zeros_like(used)
    blocked = jnp.zeros_like(used, dtype=jnp.bool_)
    cols = []
    for k in range(n_cols):
        size_k = size_ref[:, k]
        fit = used + extra + size_k <= limit
        live = live_ref[:, k] > 0.5
        if fifo:
            adm = live & fit & ~blocked
            blocked = blocked | (live & ~fit)
        else:
            adm = live & fit
        cols.append(adm.astype(jnp.float32))
        extra = extra + jnp.where(adm, size_k, 0.0)
    adm_ref[...] = jnp.stack(cols, axis=1)
    extra_ref[:, 0] = extra


def window_admit(live, size, disk_used, disk_limit, fifo: bool,
                 interpret: Optional[bool] = None):
    """Admission over a [S, C] candidate window against per-site disk
    headroom. ``fifo=False``: this tick's job arrivals (a non-fitting
    candidate is skipped); ``fifo=True``: the waiting queue (a
    non-fitting live head blocks everything behind it, §5.2).

    Returns ``(admitted [S, C] f32 mask, extra_bytes [S] f32)``.
    """
    if interpret is None:
        interpret = default_interpret()
    S, C = live.shape
    kern = functools.partial(window_kernel, n_cols=C, fifo=bool(fifo))
    adm, extra = pl.pallas_call(
        kern,
        out_shape=[
            jax.ShapeDtypeStruct((S, C), jnp.float32),
            jax.ShapeDtypeStruct((S, 1), jnp.float32),
        ],
        interpret=interpret,
    )(live.astype(jnp.float32), size,
      disk_used.reshape(S, 1), disk_limit.reshape(S, 1))
    return adm, extra[:, 0]
