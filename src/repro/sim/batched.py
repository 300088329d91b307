"""Lane-per-scenario batched sweep backend (``run_sweep(..., backend="jax")``).

The event-driven reference engine (``repro.core.hcdc``) runs one scenario
per Python interpreter; the §5.3 decision workflow wants *grids* of
scenarios. This module runs an entire packed grid as **one** ``jit`` +
``vmap`` JAX program: lane ``l`` is one ``ScenarioSpec``, every lane steps
a shared fixed-tick clock, and per-lane transfer/link state advances
through the carousel tick math in a scatter-free one-hot ``jax.numpy``
formulation (``_advance_transfers``). The paper's billing quantities — GCS
byte-seconds, tiered egress volume, class A/B operation counts — are
accumulated on device per 30-day month bucket and folded into the
existing ``GCSCostModel`` / ``MonthlyBill`` machinery on the way out, so
``backend="jax"`` returns the same ``SweepResult`` shape as the process
backend.

The tick program is **site-vectorized**: every per-site quantity lives in
an ``[S, ...]`` array and the per-tick candidate windows (this tick's job
arrivals, the waiting-queue heads) run as K/W-step prefix recurrences over
``[S, K]``/``[S, W]`` vectors, so the traced program size is O(K+W) —
independent of the site count — and shared-capacity admission (the GCS
cold tier) is a prefix-sum gate over the site-major flattened candidate
array. Consumer counts are maintained *incrementally* (O(S·K) scatters at
submission plus O(S·F) elementwise updates at file arrival) instead of a
per-tick O(S·J) segment-sum over the whole job table.

Large grids execute in bounded device memory through **lane chunking**
(``run_sweep(..., lane_chunk=)``): lanes are split into fixed-size chunks
(the last chunk padded by replication), every chunk reuses one compiled
program, and chunks round-robin across devices when more than one is
visible. ``shard=True`` replaces that Python-loop round-robin with one
``jax.shard_map`` program over a ``"lanes"`` device mesh
(``repro.parallel.sharding.lane_mesh``), and ``transport=``/``workers=``
drain lane-chunk jobs through the persistent worker fleet
(``repro.sim.runners``) — both bitwise-preserving; see
``docs/distributed.md``. ``pack_specs`` rounds the K/J job-window
shapes up to power-of-two buckets so data-dependent shapes stop forcing
recompiles.

Workloads (``repro.sim.workload``): a spec's access-pattern model
compiles to a deterministic per-generator-tick rate/popularity schedule
that ``pack_specs`` folds into the packed per-lane job stream
(``jobs_per_tick``, ``job_*``; the multipliers are exported as
``PackedGrid.rate_mult``), so non-stationary arrival shapes ride through
this backend with zero device-program changes and the grid stays a single
jit+vmap program. Workload-differing specs get distinct dynamics lanes;
only pricing-only variants share one.

Fidelity contract (cross-validated in ``tests/test_batched.py``): the
packed grid replicates the reference engine's catalogue and job-arrival
randomness draw-for-draw, while per-job file selection and run durations
come from the continuation of the same per-lane stream; the fixed tick
quantizes event times by at most one ``dt``. Per-lane jobs-done and bill
totals therefore agree with the event-driven engine within the paper's
Table 2 validation tolerance rather than bitwise (see
``docs/simulation.md`` for when the two clocks can diverge).

Per-tick phase order mirrors the reference generator: transfer advance +
completions -> link-slot FIFO admission -> hot-tier deletions & hot->cold
migrations -> job submissions -> pending-job resolution -> waiting-queue
(disk window) FIFO admission -> storage integration.
"""

from __future__ import annotations

import functools
import time
from typing import TYPE_CHECKING, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import get_registry
from repro.obs.trace import get_tracer
from repro.sim.cloud import bills_from_monthly_totals
from repro.sim.output import TimeSeries
from repro.sim.sweep import ScenarioResult, SweepResult

if TYPE_CHECKING:  # repro.core imports repro.sim; keep runtime acyclic
    from repro.core.scenarios import PackedGrid, ScenarioSpec

# File-location states; must match repro.core.hcdc.
ABSENT, IN_FLIGHT, PRESENT = 0, 1, 2

#: Disk-window (waiting queue) admissions attempted per site per tick. The
#: event engine admits any number per tick; bounding the vectorized window
#: is safe because arrivals are ~0.64 jobs/tick/site (Table 3), far below
#: it — a burst simply drains over the next few ticks.
WAIT_ADMITS_PER_TICK = 4

#: ``jax.named_scope`` of each phase of the tick, in tick order. Every
#: operation traced inside a phase carries its scope in the compiled
#: HLO's ``op_name`` metadata, so a profiler trace can be reduced by
#: phase under names that outlive a refactor of the tick. ``series``
#: exists only under ``record_series``.
TICK_SCOPES = {
    "transfer": "tick.transfer",  # transfer advance, billing, link slots
    "migrate": "tick.migrate",    # deletions, the GCS gate, migrations
    "submit": "tick.submit",      # job arrivals, pending -> ready
    "waitq": "tick.waitq",        # waiting-queue admission (W head passes)
    "apply": "tick.apply",        # deferred scatters, GB-seconds
    "series": "tick.series",      # per-tick series capture
}

_INF = jnp.float32(jnp.inf)
_NEG_INF = jnp.float32(-jnp.inf)
_BIG_TICKET = jnp.int32(2 ** 30)

#: Masks a queue head already taken, above every ticket and ``_BIG_TICKET``.
_TAKEN_TICKET = jnp.int32(np.iinfo(np.int32).max)


def _queue_heads(tickets, W: int):
    """The W lowest tickets along the last axis, as ``jax.lax.top_k(-tickets,
    W)`` gives them (negated values, then indices; equal tickets in index
    order), from W masked argmin passes.

    ``top_k`` lowers to a full sort of the file axis on the TPU, which
    the W=4 heads of a 10^6-file plane do not need: each pass is one
    reduction over the plane. ``argmin`` returns the first index of the
    minimum, and a taken slot is masked above ``_BIG_TICKET``, so
    empty slots (``_BIG_TICKET``) come out in index order as in ``top_k``.
    """
    col = jax.lax.broadcasted_iota(jnp.int32, tickets.shape,
                                   tickets.ndim - 1)
    left = tickets
    idx = []
    for _ in range(W):
        i = jnp.argmin(left, axis=-1).astype(jnp.int32)
        left = jnp.where(col == i[..., None], _TAKEN_TICKET, left)
        idx.append(i)
    idx = jnp.stack(idx, axis=-1)
    return -jnp.take_along_axis(tickets, idx, axis=-1), idx


def _count_block(n: int) -> int:
    """Block width of ``_prefix_count`` over an axis of ``n``: the largest
    divisor of ``n`` in [128, 256], so that the axis splits into blocks
    without a pad, else 128 with the axis padded. The matmul's work per
    file grows with the width; one or two 128-lane tiles hold a block."""
    return next((b for b in range(256, 127, -1) if n % b == 0), 128)


def _prefix_count(mask):
    """Inclusive count of ``mask`` along its last axis, as float32:
    bitwise ``jnp.cumsum(mask.astype(jnp.float32), axis=-1)``.

    On the TPU a cumsum lowers to a tree of ``reduce-window``s that runs
    at about a twentieth of the memory bound over a 10^6-file plane.
    Here the axis is cut into blocks of ``_count_block`` files: one
    ``[B, B]`` upper-triangular matmul of ones counts within each block
    on the MXU, and a cumsum of the block totals, B times shorter, places
    the blocks. The operands are exactly 0 and 1 in bf16 and the matmul
    accumulates in f32, so every partial count is an integer, exact up
    to 2^24: any order of summation gives the cumsum's bits.
    """
    n = mask.shape[-1]
    B = _count_block(n)
    blocks = -(-n // B)
    lead = mask.shape[:-1]
    x = mask.astype(jnp.bfloat16)
    if blocks * B != n:
        x = jnp.pad(x, [(0, 0)] * len(lead) + [(0, blocks * B - n)])
    row = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    upper = (row <= col).astype(jnp.bfloat16)
    within = jnp.matmul(x.reshape(*lead, blocks, B), upper,
                        preferred_element_type=jnp.float32)
    totals = within[..., -1]
    before = jnp.cumsum(totals, axis=-1) - totals
    count = (within + before[..., None]).reshape(*lead, blocks * B)
    return count[..., :n]


def _migration_plan(mig, q_empty, free_m):
    """Where each site's migrations ``mig`` go on its disk->cloud link,
    FIFO in file order: ``(direct, queued, qrank)``.

    ``direct`` take a free slot now: the first ``free_m`` migrations, and
    only while the link queue is empty (``q_empty``). ``queued`` join the
    queue; ``qrank`` is the ordinal of each among the site's queued ones,
    read only where ``queued``. The direct ones are a site's first
    migrations, so a queued one's ordinal is its rank less their count:
    one prefix count serves both.
    """
    rank = _prefix_count(mig) - 1.0
    direct = mig & q_empty & (rank < free_m)
    queued = mig & ~direct
    qrank = rank.astype(jnp.int32) - jnp.sum(direct, axis=-1, keepdims=True)
    return direct, queued, qrank


def _vary_like(tree, ref):
    """Cast ``tree`` to vary over the mesh axes ``ref`` varies over.

    Under ``shard_map`` the lane inputs vary over the mesh axis while
    freshly made constants do not, and a loop requires its carry's
    varying axes to match its body's output. The cast is type-level, has
    no effect on the values, and is a no-op outside ``shard_map``."""
    vma = jax.typeof(ref).vma

    def cast(x):
        axes = tuple(sorted(vma - jax.typeof(x).vma))
        return jax.lax.pcast(x, axes, to="varying") if axes else x

    return jax.tree.map(cast, tree) if vma else tree


def _gcs_first_fit(want, sizes, used, limit):
    """The shared cloud bucket's admission gate, first fit.

    Scanning the candidates of ``want`` in order (the ``[S, F]`` planes
    flattened site-major), a candidate is admitted iff the bytes in the
    bucket (``used``), plus those admitted before it, plus its own size
    are at most ``limit``: the event engine's greedy scan, where a file
    too large for the room left waits and a smaller one behind it may
    still go. Returns ``(admitted, used', passes)``.

    Each pass takes the room ``R = limit - used``, drops the candidates
    larger than ``R`` (they cannot fit for the rest of the tick: the
    room only shrinks), and admits the prefix of the rest whose cumsum
    is at most ``R``. That prefix is what first fit admits up to the
    next candidate that does not fit, so the passes reproduce first fit
    exactly. A pass admits at least its first candidate, so the loop
    ends. An unlimited bucket takes one pass on a tick with candidates,
    and a tick with none (or a disabled bucket) takes none.

    The loop carries the planes in their own shape and each pass works
    on their flattened views: the compiler then reduces the admitted
    bytes in the shape it reduced them in before the loop existed (on a
    TPU the order of a float sum follows the shape it reduces), and an
    unlimited bucket's level stays bitwise what it was.
    """
    sizes_flat = sizes.reshape(-1)

    def fitting(admitted, used):
        return want & ~admitted & (sizes <= limit - used)

    def one_pass(carry):
        admitted, used, rem, passes = carry
        rem_flat = rem.reshape(-1)
        csum = jnp.cumsum(sizes_flat * rem_flat)
        new = rem_flat & (csum <= limit - used)
        used = used + jnp.sum(sizes_flat * new)
        admitted = admitted | new.reshape(rem.shape)
        return admitted, used, fitting(admitted, used), passes + 1

    admitted = jnp.zeros_like(want)
    init = _vary_like((admitted, used, fitting(admitted, used),
                       jnp.int32(0)), want)
    admitted, used, _, passes = jax.lax.while_loop(
        lambda carry: jnp.any(carry[2]), one_pass, init)
    return admitted, used, passes


def _per_link(table, ltype):
    """``table[link]`` for every file row of ``[S, F]``, where ``table``
    is indexed by link id ``3 * site + type`` and ``ltype`` is each row's
    link type: a select over the type instead of a gather. A file uses
    only its own site's links. In XLA's cost model for a TPU v5e, five
    such [S, F] gathers were about 94% of the paper-width tick's memory
    traffic. Rows with no transfer yet hold link 0 and read their own
    site's type-0 entry; every use masks those rows."""
    t3 = table.reshape(ltype.shape[0], 3)
    return jnp.where(ltype == 0, t3[:, 0:1],
                     jnp.where(ltype == 1, t3[:, 1:2], t3[:, 2:3]))


def _advance_transfers(active, ltype, loc_onehot, done, total, bw, mode, dt):
    """Advance every transfer in ``active`` by one tick of ``dt`` seconds
    (the carousel tick math) over the ``[S, F]`` file planes.

    A file only ever transfers on its own site's three links (link id
    ``3 * site + type``; ``ltype`` is the type, ``loc_onehot`` its
    one-hot ``[S, F, 3]``), so the per-link active counts are a one-hot
    reduction over the type axis with no scatter (XLA:CPU expands
    scatters into O(S·F)-trip sequential loops). A link in dedicated
    mode (``mode > 0``) gives every transfer its full bandwidth ``bw``;
    a shared link splits ``bw`` evenly among its active transfers.

    Returns ``(new_done, comp, by_type)``: the bytes done per file,
    capped at ``total``; the completion mask; and that mask split by
    link type (tape->disk, gcs->disk, disk->gcs). The tick bills each
    type's completed bytes, ``sum(sizes * by_type[k], axis=1)``, in
    between its own state updates.
    """
    act_f = active.astype(jnp.float32)
    counts = jnp.sum(act_f[:, :, None] * loc_onehot,
                     axis=1).reshape(-1)  # [M], M = 3*S
    bw_i = _per_link(bw, ltype)
    shared = bw_i / jnp.maximum(_per_link(counts, ltype), 1.0)
    rate = jnp.where(_per_link(mode, ltype) > 0, bw_i, shared)
    new_done = jnp.minimum(total, done + act_f * rate * dt)
    comp = (new_done >= total) & active
    return new_done, comp, tuple(comp & (ltype == k) for k in range(3))


def _window_admit(live, size, used, limit, fifo, stale=None):
    """Admit a per-site candidate window against the disk limit, in
    window order: ``(admitted [S, C], extra [S])``.

    Candidate ``k`` of a site is admitted iff it is ``live`` (and not
    ``stale``, where given) and the site's disk (``used``), plus the
    sizes admitted before it in the window, plus its own ``size`` stays
    within ``limit``; ``extra`` is the bytes admitted. The window is
    tiny, so the recurrence runs as C steps over ``[S]`` vectors, all
    sites together. ``fifo=False`` is the K-window of this tick's job
    arrivals: a candidate that does not fit is passed over. ``fifo=True``
    is the waiting queue's strict FIFO (§5.2): the first live candidate
    that does not fit blocks the rest, and a stale one is passed over.
    """
    cols = []
    extra = jnp.zeros_like(used)
    blocked = jnp.zeros(used.shape, bool) if fifo else None
    for k in range(live.shape[1]):
        fit = used + extra + size[:, k] <= limit
        live_k = live[:, k] if stale is None else live[:, k] & ~stale[:, k]
        adm = live_k & fit
        if fifo:
            adm = adm & ~blocked
            blocked = blocked | (live_k & ~fit)
        cols.append(adm)
        extra = extra + jnp.where(adm, size[:, k], 0.0)
    return jnp.stack(cols, axis=1), extra


#: Per-site link-type order of the captured link-activity series (the
#: ``3 * site + type`` link-id layout).
LINK_TYPES = ("tape_to_disk", "gcs_to_disk", "disk_to_gcs")


def _normalize_record(record_series, n_ticks: int):
    """Normalize a ``record_series=`` argument to ``(stride, n_samples)``
    (or ``None`` when capture is off). ``True`` samples every tick; an
    int samples every that-many ticks (tick 0 always sampled)."""
    if record_series is None or record_series is False:
        return None
    stride = 1 if record_series is True else int(record_series)
    if stride < 1:
        raise ValueError(f"record_series must be >= 1, got {record_series!r}")
    return stride, (n_ticks - 1) // stride + 1


def _lane_step_fns(S: int, K: int, n_months: int, record=None):
    """Build the per-lane tick body and post-scan reduction (closures over
    the static dimensions).

    Vectorization notes: the per-tick candidate sets (this tick's job
    arrivals, the waiting-queue window) are tiny, so their sequential
    semantics — later candidates see earlier reservations — are computed
    as K/W-step prefix recurrences over ``[S, K]``/``[S, W]`` vectors (all
    sites advance together; the traced program is O(K+W), not O(S·(K+W))),
    and the results land in the big ``[S, F]`` state arrays through *one*
    scatter per array. Scatters use duplicate-safe combinators (``add`` of
    deltas, ``max``/``min`` for flags) because the same file id can appear
    several times in a candidate window.

    Consumer counts (jobs holding a file on the hot tier) are incremental:

    - ``pend_cnt``/``pend_tail`` [S,F]: count and max run-tail of jobs
      submitted whose input file is not yet on disk (+1/+max scatters over
      the K-window at submission; zeroed elementwise when the file
      arrives);
    - ``fin_max`` [S,F]: max analytic finish time (``ready + tail``) over
      jobs whose input is on disk (max-scatter at submission onto present
      files; elementwise ``now + pend_tail`` fold when a file arrives).

    A file has no consumers iff ``pend_cnt == 0`` and ``fin_max <= now`` —
    exactly the condition the previous per-tick segment-sum over the whole
    [S, J] job table computed, at a fraction of the cost.

    ``record`` (``(stride, n_samples)`` or ``None``) turns on per-tick
    series capture: ring buffers sized ``[n_samples + 1, ...]`` ride in
    the scan carry and every tick writes its end-of-tick observables —
    disk/GCS occupancy, waiting-queue depth, running jobs, per-link
    active transfers — at ``t // stride`` when ``t`` is a sample tick
    and into the final *trash slot* otherwise (dropped by ``post_fn``),
    so the per-tick cost stays O(S) and memory O(n_samples * S) per
    lane. With ``record=None`` the carry, the traced program, and the
    results are byte-for-byte the pre-capture ones.

    Each phase of the tick runs under its ``TICK_SCOPES`` name; the
    scopes are metadata and leave the program's operations unchanged.
    """

    def tick_fn(state, xs, const):
        now, dt, month, t, jobs_now = xs
        (sizes, pop, job_fid, job_submit_tick, job_tail, disk_limit,
         gcs_enabled, gcs_limit, min_pop, bw, slots, latency, mode) = const
        F = sizes.shape[1]
        J = job_fid.shape[1]
        st = dict(state)
        site_rows = jnp.arange(S, dtype=jnp.int32)

        with jax.named_scope(TICK_SCOPES["transfer"]):
            # -- consumer snapshot (jobs submitted strictly before this tick
            # that have not finished by ``now``; deletions run before
            # submissions in the reference generator, so this tick's arrivals
            # are excluded — their scatters land at the end of the tick).
            no_cons = (st["pend_cnt"] == 0) & (st["fin_max"] <= now)

            # -- advance transfers one tick (the carousel tick math)
            now_prev = now - dt
            t_active = st["tr_slot"] & (st["tr_start"] <= now_prev + 0.5)
            ltype = st["tr_link"] % 3  # 0 tape->disk, 1 gcs->disk, 2 disk->gcs
            loc_onehot = ltype[:, :, None] == jnp.arange(3, dtype=jnp.int32)
            new_done, comp, by_type = _advance_transfers(
                t_active, ltype, loc_onehot, st["tr_done"], st["tr_total"],
                bw, mode, dt)
            comp_tape, comp_recall, comp_mig = by_type
            inbound = comp_tape | comp_recall

            st["disk_state"] = jnp.where(inbound, PRESENT, st["disk_state"])
            st["gcs_state"] = jnp.where(comp_mig, PRESENT, st["gcs_state"])
            st["tape_b"] += jnp.sum(sizes * comp_tape, axis=1)
            st["gcsdisk_b"] += jnp.sum(sizes * comp_recall, axis=1)
            recall_bytes = jnp.sum(sizes * comp_recall)
            st["egress_mo"] = st["egress_mo"].at[month].add(recall_bytes)
            st["cls_b_mo"] = st["cls_b_mo"].at[month].add(
                jnp.sum(comp_recall).astype(jnp.float32))
            st["diskgcs_b"] += jnp.sum(sizes * comp_mig, axis=1)
            st["cls_a_mo"] = st["cls_a_mo"].at[month].add(
                jnp.sum(comp_mig).astype(jnp.float32))
            # migrated with no remaining consumer: drop the hot copy now
            drop_hot = comp_mig & no_cons & (st["disk_state"] == PRESENT)
            st["disk_used"] -= jnp.sum(sizes * drop_hot, axis=1)
            st["disk_state"] = jnp.where(drop_hot, ABSENT, st["disk_state"])
            st["tr_slot"] = st["tr_slot"] & ~comp
            st["tr_done"] = jnp.where(comp, 0.0, new_done)
            st["tr_total"] = jnp.where(comp, _INF, st["tr_total"])
            st["tr_start"] = jnp.where(comp, _INF, st["tr_start"])

            # arrived files resolve their pending jobs (ready is assigned in
            # the pending step below with the same ``now``): the pending count
            # folds into the analytic finish horizon.
            resolve = inbound & (st["pend_cnt"] > 0)
            st["fin_max"] = jnp.where(
                resolve, jnp.maximum(st["fin_max"], now + st["pend_tail"]),
                st["fin_max"])
            st["pend_cnt"] = jnp.where(inbound, 0, st["pend_cnt"])
            st["pend_tail"] = jnp.where(inbound, 0.0, st["pend_tail"])

            # -- link-slot FIFO admission (tickets are contiguous per link).
            # Link-indexed counters live as [S, 3] matrices (site x link type)
            # so every update is a static column slice, never a scatter.
            occ3 = jnp.sum(st["tr_slot"].astype(jnp.float32)[:, :, None]
                           * loc_onehot, axis=1)  # [S, 3] active-slot counts
            occ = occ3.reshape(-1)
            free = jnp.maximum(slots - occ, 0.0)
            n_q = (st["lq_next"] - st["lq_serve"]).astype(jnp.float32)
            admit = jnp.minimum(free, n_q).astype(jnp.int32)
            new_serve = st["lq_serve"] + admit
            adm_row = st["lq_queued"] & \
                (st["lq_ticket"] < _per_link(new_serve, ltype))
            st["tr_slot"] = st["tr_slot"] | adm_row
            st["tr_start"] = jnp.where(
                adm_row, now + _per_link(latency, ltype), st["tr_start"])
            st["lq_queued"] = st["lq_queued"] & ~adm_row
            st["lq_serve"] = new_serve
            occ3 = (occ + admit.astype(jnp.float32)).reshape(S, 3)
            lqn3 = st["lq_next"].reshape(S, 3)   # working [S, 3] views; the
            lqs3 = st["lq_serve"].reshape(S, 3)  # flat [M] state is written
            slots3 = slots.reshape(S, 3)         # back after the windows
            lat3 = latency.reshape(S, 3)

        with jax.named_scope(TICK_SCOPES["migrate"]):
            # -- hot-tier deletions + hot->cold migrations --------------------
            limited = jnp.isfinite(disk_limit)[:, None]
            cand = no_cons & (st["disk_state"] == PRESENT) & limited
            gs = st["gcs_state"]
            migratable = gcs_enabled & (gs == ABSENT) & (pop >= min_pop)
            delete = cand & (~gcs_enabled | (gs == PRESENT)
                             | ((gs == ABSENT) & ~(pop >= min_pop)))
            want_mig = cand & migratable
            # Shared GCS capacity: first fit over the site-major flattened
            # candidate vector (``_gcs_first_fit``), so earlier sites' and
            # files' admissions are visible to later ones and a file too
            # large for the room left waits on disk for a later tick.
            mig, gcs_used, passes = _gcs_first_fit(
                want_mig, sizes, st["gcs_used"], gcs_limit)
            refused = jnp.any(want_mig & ~mig)
            st["gcs_used"] = gcs_used
            st["gate_passes"] = st["gate_passes"] + passes
            st["refused_ticks"] = st["refused_ticks"] + refused.astype(
                jnp.int32)
            st["first_refusal"] = jnp.minimum(
                st["first_refusal"], jnp.where(refused, now, _INF))
            st["gcs_state"] = jnp.where(mig, IN_FLIGHT, gs)
            st["disk_used"] -= jnp.sum(sizes * delete, axis=1)
            st["disk_state"] = jnp.where(delete, ABSENT, st["disk_state"])
            # submit migrations on each site's disk->gcs link (FIFO: direct
            # slots only while the link queue is empty, overflow queues)
            mlink = 3 * site_rows + 2  # [S]
            q_empty = (lqn3[:, 2] == lqs3[:, 2])[:, None]
            free_m = jnp.maximum(slots3[:, 2] - occ3[:, 2], 0.0)[:, None]
            direct, queued, qrank = _migration_plan(mig, q_empty, free_m)
            st["tr_slot"] = st["tr_slot"] | direct
            st["tr_link"] = jnp.where(mig, mlink[:, None], st["tr_link"])
            st["tr_total"] = jnp.where(mig, sizes, st["tr_total"])
            st["tr_done"] = jnp.where(mig, 0.0, st["tr_done"])
            st["tr_start"] = jnp.where(direct, now, st["tr_start"])
            st["lq_ticket"] = jnp.where(
                queued, lqn3[:, 2][:, None] + qrank, st["lq_ticket"])
            st["lq_queued"] = st["lq_queued"] | queued
            lqn3 = lqn3.at[:, 2].add(
                jnp.sum(queued, axis=1).astype(jnp.int32))
            occ3 = occ3.at[:, 2].add(
                jnp.sum(direct, axis=1).astype(jnp.float32))

        # =================================================================
        # Candidate-window planning, site-batched. This tick's job arrivals
        # (K per site) and the waiting-queue heads (W per site) are tiny
        # windows; their sequential semantics — later candidates see
        # earlier reservations — run as K/W-step prefix recurrences over
        # [S, K]/[S, W] vectors, and every resulting state change is
        # DEFERRED and applied below as a single duplicate-safe scatter
        # per array.
        # =================================================================
        W = WAIT_ADMITS_PER_TICK
        plans = []  # per group: dict of planned per-candidate [S, C] vecs

        def plan_links(fids, fire, occ3):
            """Assign link slots / FIFO queue tickets to fired candidates
            (``fids``/``fire`` are [S, C]; candidate windows only touch
            their own site's tape->disk / gcs->disk links, so all sites
            plan in parallel).

            Mutates only the small [S, 3] occupancy/ticket counters;
            returns the per-candidate plan (direct slot, queue ticket,
            start time).
            """
            from_gcs = gcs_enabled & (
                jnp.take_along_axis(st["gcs_state"], fids, axis=1)
                == PRESENT)
            link_local = jnp.where(from_gcs, 1, 0)
            direct = jnp.zeros_like(fire)
            queued = jnp.zeros_like(fire)
            tstart = jnp.full(fire.shape, jnp.inf, jnp.float32)
            lq_val = jnp.zeros(fire.shape, jnp.int32)
            nonlocal lqn3
            for loc in (0, 1):  # tape->disk, gcs->disk
                mask = fire & (link_local == loc)
                q_empty = (lqn3[:, loc] == lqs3[:, loc])[:, None]
                free_m = jnp.maximum(slots3[:, loc] - occ3[:, loc],
                                     0.0)[:, None]
                rk = jnp.cumsum(mask.astype(jnp.float32), axis=1) - 1.0
                d = mask & q_empty & (rk < free_m)
                qd = mask & ~d
                qrk = jnp.cumsum(qd.astype(jnp.int32), axis=1) - 1
                direct = direct | d
                queued = queued | qd
                tstart = jnp.where(d, now + lat3[:, loc][:, None], tstart)
                lq_val = jnp.where(qd, lqn3[:, loc][:, None] + qrk,
                                   lq_val)
                lqn3 = lqn3.at[:, loc].add(
                    jnp.sum(qd, axis=1).astype(jnp.int32))
                occ3 = occ3.at[:, loc].add(
                    jnp.sum(d, axis=1).astype(jnp.float32))
            rows = site_rows[:, None] * F + fids
            return occ3, dict(rows=rows, fire=fire,
                             m_vec=3 * site_rows[:, None] + link_local,
                             direct=direct, queued=queued, tstart=tstart,
                             lq_val=lq_val)

        with jax.named_scope(TICK_SCOPES["submit"]):
            # -- group 1: job submissions for this tick (only the first arrival
            # of a file starts its transfer; later same-tick jobs attach) -----
            started = jnp.zeros((S, 0), bool)
            g1_fids = jnp.zeros((S, 0), jnp.int32)
            if K > 0:
                ks = jnp.arange(K, dtype=jnp.int32)
                jpos = st["ptr"][:, None] + ks[None, :]  # [S, K]
                jid = jnp.minimum(jpos, J - 1)
                valid = (jpos < J) & \
                    (jnp.take_along_axis(job_submit_tick, jid, axis=1) == t)
                fids = jnp.take_along_axis(job_fid, jid, axis=1)
                g1_fids = fids
                # same[s, k, j]: an earlier valid window slot j < k carries the
                # same file — slot k attaches instead of starting a transfer.
                same = (fids[:, None, :] == fids[:, :, None]) \
                    & valid[:, None, :] \
                    & (ks[None, None, :] < ks[None, :, None])
                first = valid & ~jnp.any(same, axis=2)
                size = jnp.take_along_axis(sizes, fids, axis=1)
                ds = jnp.take_along_axis(st["disk_state"], fids, axis=1)
                ww = jnp.take_along_axis(st["wq_wait"], fids, axis=1)
                tailw = jnp.take_along_axis(job_tail, jid, axis=1)
                absent = first & (ds == ABSENT)
                started, extra = _window_admit(absent, size,
                                               st["disk_used"], disk_limit,
                                               fifo=False)
                st["disk_used"] = st["disk_used"] + extra
                to_wait = absent & ~started & ~ww
                wrank = jnp.cumsum(to_wait.astype(jnp.int32), axis=1) - 1
                occ3, plan = plan_links(fids, started, occ3)
                plan["to_wait"] = to_wait
                plan["wq_val"] = jnp.where(to_wait,
                                           st["wq_next"][:, None] + wrank, 0)
                st["wq_next"] = st["wq_next"] + \
                    jnp.sum(to_wait, axis=1).astype(jnp.int32)
                plan["stale"] = jnp.zeros_like(started)
                # incremental consumer deltas: window jobs whose file is on
                # disk are ready this tick (analytic finish now + tail); the
                # rest join the pending pool on their file.
                ready_now = valid & (ds == PRESENT)
                plan["pend_add"] = valid & ~ready_now
                plan["fin_val"] = jnp.where(ready_now, now + tailw, _NEG_INF)
                plan["tail"] = tailw
                plans.append(plan)
            st["ptr"] = st["ptr"] + jobs_now

        with jax.named_scope(TICK_SCOPES["waitq"]):
            # -- group 2: waiting-queue admission — strict FIFO on the disk
            # window; the head blocks admission until its file fits (§5.2).
            # Planned from the pre-scatter queue state: entries started above
            # (queue-jump) are excluded by fid comparison; entries enqueued
            # above are not yet visible (they join next tick, matching a tail
            # position in the FIFO).
            tickets = jnp.where(st["wq_wait"], st["wq_ticket"], _BIG_TICKET)
            neg, idx = _queue_heads(tickets, W)  # [S, W] lowest tickets
            validw = neg > -_BIG_TICKET
            jumped = jnp.zeros(idx.shape, bool)
            if K > 0:
                started_fid = jnp.where(started, g1_fids, -1)  # [S, K]
                jumped = jnp.any(idx[:, :, None] == started_fid[:, None, :],
                                 axis=2)
            ds = jnp.take_along_axis(st["disk_state"], idx, axis=1)
            stale = validw & ((ds != ABSENT) | jumped)
            size = jnp.take_along_axis(sizes, idx, axis=1)
            admitted, extra = _window_admit(validw, size, st["disk_used"],
                                            disk_limit, fifo=True,
                                            stale=stale)
            st["disk_used"] = st["disk_used"] + extra
            occ3, plan = plan_links(idx, admitted, occ3)
            plan["stale"] = stale
            plans.append(plan)

            st["lq_next"] = lqn3.reshape(-1)

        with jax.named_scope(TICK_SCOPES["submit"]):
            # -- pending jobs whose input is on disk enter queued -> running;
            # completion is analytic (ready + download + duration). Planned
            # starts only flip ABSENT -> IN_FLIGHT, so the pre-scatter
            # disk_state is PRESENT-accurate here. ----------------------------
            pending = (job_submit_tick <= t) & (st["job_ready"] >= _INF)
            on_disk = jnp.take_along_axis(st["disk_state"], job_fid,
                                          axis=1) == PRESENT
            st["job_ready"] = jnp.where(pending & on_disk, now,
                                         st["job_ready"])

        with jax.named_scope(TICK_SCOPES["apply"]):
            # -- apply the planned windows: one scatter per state array.
            # XLA:CPU expands each scatter into a sequential per-row loop, so
            # rows are kept to the minimum: transfer/link plans scatter over
            # both windows; the submission-only fields (wait-queue joins and
            # the incremental consumer counters) exist only in the K-window
            # and scatter over a third of the rows.
            def cat(key):
                return jnp.concatenate([p[key].reshape(-1) for p in plans])

            rows = cat("rows")
            fire = cat("fire")
            stale = cat("stale")
            m_vec = cat("m_vec")
            direct = cat("direct")
            queued = cat("queued")
            tstart = cat("tstart")
            lq_val = cat("lq_val")
            size_c = sizes.reshape(-1)[rows]

            def flat(name, update):
                st[name] = update(st[name].reshape(-1)).reshape(S, F)

            cur_link = st["tr_link"].reshape(-1)[rows]
            cur_lqt = st["lq_ticket"].reshape(-1)[rows]
            flat("disk_state", lambda a: a.at[rows].add(
                jnp.where(fire, IN_FLIGHT - ABSENT, 0)))
            # started/stale entries leave the wait queue (new waiters join in
            # the K-window block below, preserving the min-before-max order)
            flat("wq_wait", lambda a: a.at[rows].min(~(fire | stale)))
            flat("tr_link", lambda a: a.at[rows].add(
                jnp.where(fire, m_vec - cur_link, 0)))
            flat("tr_total", lambda a: a.at[rows].min(
                jnp.where(fire, size_c, _INF)))
            flat("tr_slot", lambda a: a.at[rows].max(direct))
            flat("tr_start", lambda a: a.at[rows].min(tstart))
            flat("lq_ticket", lambda a: a.at[rows].add(
                jnp.where(queued, lq_val - cur_lqt, 0)))
            flat("lq_queued", lambda a: a.at[rows].max(queued))

            if K > 0:  # K-window-only scatters (wait-queue joins + consumers)
                g1 = plans[0]
                rows1 = g1["rows"].reshape(-1)
                to_wait = g1["to_wait"].reshape(-1)
                wq_val = g1["wq_val"].reshape(-1)
                pend_add = g1["pend_add"].reshape(-1)
                fin_val = g1["fin_val"].reshape(-1)
                tail_c = g1["tail"].reshape(-1)
                cur_wqt = st["wq_ticket"].reshape(-1)[rows1]
                flat("wq_wait", lambda a: a.at[rows1].max(to_wait))
                flat("wq_ticket", lambda a: a.at[rows1].add(
                    jnp.where(to_wait, wq_val - cur_wqt, 0)))
                # incremental consumer counters (visible from the next tick
                # on, matching the reference's deletions-before-submissions)
                flat("pend_cnt", lambda a: a.at[rows1].add(
                    jnp.where(pend_add, 1, 0)))
                flat("pend_tail", lambda a: a.at[rows1].max(
                    jnp.where(pend_add, tail_c, 0.0)))
                flat("fin_max", lambda a: a.at[rows1].max(fin_val))

            # -- integrate stored cloud volume (GB-seconds) per month ---------
            st["gbsec_mo"] = st["gbsec_mo"].at[month].add(
                st["gcs_used"] / 1e9 * dt)

        with jax.named_scope(TICK_SCOPES["series"]):
            # -- opt-in series capture (end-of-tick observables) --------------
            if record is not None:
                stride, n_samples = record
                idx = jnp.where(t % stride == 0, t // stride,
                                jnp.int32(n_samples))
                queue = jnp.sum(st["wq_wait"], axis=1).astype(jnp.float32)
                running = jnp.sum(
                    (st["job_ready"] < _INF)
                    & (st["job_ready"] + job_tail > now),
                    axis=1).astype(jnp.float32)
                active3 = jnp.sum(
                    st["tr_slot"].astype(jnp.float32)[:, :, None]
                    * ((st["tr_link"] % 3)[:, :, None]
                       == jnp.arange(3, dtype=jnp.int32)), axis=1)  # [S, 3]
                upd = jax.lax.dynamic_update_index_in_dim
                st["ser_disk"] = upd(st["ser_disk"], st["disk_used"], idx, 0)
                st["ser_gcs"] = upd(st["ser_gcs"], st["gcs_used"], idx, 0)
                st["ser_queue"] = upd(st["ser_queue"], queue, idx, 0)
                st["ser_run"] = upd(st["ser_run"], running, idx, 0)
                st["ser_link"] = upd(st["ser_link"], active3, idx, 0)
        return st, None

    def post_fn(st, lane, horizon):
        (sizes, job_fid, job_submit_time, job_tail) = lane
        ready = st["job_ready"] < _INF
        done = ready & (st["job_ready"] + job_tail <= horizon)
        job_sizes = jnp.take_along_axis(sizes, job_fid, axis=1)
        wait_h = (st["job_ready"] - job_submit_time) / 3600.0
        series = {}
        if record is not None:
            n_samples = record[1]  # drop the trash slot
            series = {k: st[k][:n_samples]
                      for k in ("ser_disk", "ser_gcs", "ser_queue",
                                "ser_run", "ser_link")}
        return {
            **series,
            "jobs_done_site": jnp.sum(done, axis=1),
            "download_b": jnp.sum(job_sizes * ready, axis=1),
            "wait_h_sum": jnp.sum(jnp.where(ready, wait_h, 0.0)),
            "wait_n": jnp.sum(ready),
            "disk_used": st["disk_used"],
            "gcs_used": st["gcs_used"],
            "tape_b": st["tape_b"],
            "gcsdisk_b": st["gcsdisk_b"],
            "diskgcs_b": st["diskgcs_b"],
            "egress_mo": st["egress_mo"],
            "cls_a_mo": st["cls_a_mo"],
            "cls_b_mo": st["cls_b_mo"],
            "gbsec_mo": st["gbsec_mo"],
            "gcs_gate_passes": st["gate_passes"],
            "gcs_refused_ticks": st["refused_ticks"],
            "gcs_first_refusal_s": st["first_refusal"],
        }

    return tick_fn, post_fn


def _build_lane_sim(S: int, K: int, n_months: int, record=None):
    """The single-lane simulation function (closure over the static
    dimensions): 5 shared tick-grid arguments + the 15 ``_LANE_FIELDS``
    arrays -> the per-lane aggregate dict. ``_grid_program`` vmaps it
    over the lane axis; ``_shard_program`` additionally shard_maps the
    vmapped program over a device mesh."""
    tick_fn, post_fn = _lane_step_fns(S, K, n_months, record=record)

    def lane_sim(times, dts, month_idx, t_idx, horizon,
                 disk_limit, gcs_enabled, gcs_limit, min_pop,
                 bw, slots, latency, mode, sizes, pop,
                 job_fid, job_submit_tick, job_submit_time, job_tail,
                 jobs_per_tick):
        F = sizes.shape[1]
        J = job_fid.shape[1]
        M = bw.shape[0]
        const = (sizes, pop, job_fid, job_submit_tick, job_tail,
                 disk_limit, gcs_enabled, gcs_limit, min_pop,
                 bw, slots, latency, mode)
        init = dict(
            disk_state=jnp.zeros((S, F), jnp.int32),
            gcs_state=jnp.zeros((S, F), jnp.int32),
            disk_used=jnp.zeros((S,), jnp.float32),
            gcs_used=jnp.float32(0.0),
            tr_slot=jnp.zeros((S, F), bool),
            tr_link=jnp.zeros((S, F), jnp.int32),
            tr_done=jnp.zeros((S, F), jnp.float32),
            tr_total=jnp.full((S, F), jnp.inf, jnp.float32),
            tr_start=jnp.full((S, F), jnp.inf, jnp.float32),
            lq_ticket=jnp.zeros((S, F), jnp.int32),
            lq_queued=jnp.zeros((S, F), bool),
            lq_serve=jnp.zeros((M,), jnp.int32),
            lq_next=jnp.zeros((M,), jnp.int32),
            wq_wait=jnp.zeros((S, F), bool),
            wq_ticket=jnp.zeros((S, F), jnp.int32),
            wq_next=jnp.zeros((S,), jnp.int32),
            pend_cnt=jnp.zeros((S, F), jnp.int32),
            pend_tail=jnp.zeros((S, F), jnp.float32),
            fin_max=jnp.zeros((S, F), jnp.float32),
            job_ready=jnp.full((S, J), jnp.inf, jnp.float32),
            ptr=jnp.zeros((S,), jnp.int32),
            tape_b=jnp.zeros((S,), jnp.float32),
            gcsdisk_b=jnp.zeros((S,), jnp.float32),
            diskgcs_b=jnp.zeros((S,), jnp.float32),
            egress_mo=jnp.zeros((n_months,), jnp.float32),
            cls_a_mo=jnp.zeros((n_months,), jnp.float32),
            cls_b_mo=jnp.zeros((n_months,), jnp.float32),
            gbsec_mo=jnp.zeros((n_months,), jnp.float32),
            # the cloud admission gate's counters (``_gcs_first_fit``):
            # passes run, ticks that refused a candidate for lack of room,
            # and the time of the first such tick (inf: none)
            gate_passes=jnp.int32(0),
            refused_ticks=jnp.int32(0),
            first_refusal=jnp.float32(jnp.inf),
        )
        if record is not None:
            n_samples = record[1]  # +1 = the non-sample-tick trash slot
            init.update(
                ser_disk=jnp.zeros((n_samples + 1, S), jnp.float32),
                ser_gcs=jnp.zeros((n_samples + 1,), jnp.float32),
                ser_queue=jnp.zeros((n_samples + 1, S), jnp.float32),
                ser_run=jnp.zeros((n_samples + 1, S), jnp.float32),
                ser_link=jnp.zeros((n_samples + 1, S, 3), jnp.float32),
            )
        # the scan's carry has to vary like the tick's output
        init = _vary_like(init, sizes)
        final, _ = jax.lax.scan(
            lambda c, xs: tick_fn(c, xs, const), init,
            (times, dts, month_idx, t_idx, jobs_per_tick))
        return post_fn(final, (sizes, job_fid, job_submit_time, job_tail),
                       horizon)

    return lane_sim


#: vmap axes of ``lane_sim``: 5 shared tick-grid args + 15 lane arrays.
_LANE_AXES = (None, None, None, None, None) + (0,) * 15


@functools.lru_cache(maxsize=16)
def _grid_program(S: int, K: int, n_months: int, impl_name: str,
                  record=None):
    """The jitted lane-vmapped simulation (cached per static shape family
    and series-capture configuration; XLA additionally retraces per
    concrete array shape — ``pack_specs``'s K/J power-of-two bucketing
    and ``lane_chunk`` keep those shapes stable across grids).

    ``impl_name`` must be ``"jnp"``, the name of the one tick
    implementation (``repro.kernels.registry``); it is checked and
    otherwise unused. Callers pass all five arguments positionally, so
    that one shape family has one cache entry whoever asks for it."""
    if impl_name != "jnp":
        raise ValueError(f"unknown tick implementation {impl_name!r} "
                         "(the batched engine has one: 'jnp')")
    lane_sim = _build_lane_sim(S, K, n_months, record)
    return jax.jit(jax.vmap(lane_sim, in_axes=_LANE_AXES))


@functools.lru_cache(maxsize=16)
def _shard_program(S: int, K: int, n_months: int, record, n_shards: int):
    """The sharded grid program: ``shard_map`` of the lane-vmapped
    simulation over a ``n_shards``-device ``"lanes"`` mesh
    (``repro.parallel.sharding.lane_mesh``).

    Each device runs the identical vmapped per-lane program on its
    1/``n_shards`` slice of the lane batch — lanes never interact, so
    there are no collectives and per-lane results are bitwise identical
    to the unsharded program (asserted in ``tests/test_batched.py``).
    The lane-axis extent of every lane argument must divide
    ``n_shards``; callers pad by replicating the last lane, exactly as
    the chunked path does. The 5 shared tick-grid arguments are
    replicated to every device."""
    from repro.parallel.sharding import LANES_AXIS, lane_mesh

    lane_sim = _build_lane_sim(S, K, n_months, record)
    mesh = lane_mesh(n_shards)
    P = jax.sharding.PartitionSpec
    in_specs = (P(),) * 5 + (P(LANES_AXIS),) * 15
    sharded = jax.shard_map(
        jax.vmap(lane_sim, in_axes=_LANE_AXES), mesh=mesh,
        in_specs=in_specs, out_specs=P(LANES_AXIS))
    return jax.jit(sharded)


#: Per-lane array attributes of ``PackedGrid``, in ``lane_sim`` argument
#: order (after the five shared tick-grid arguments).
_LANE_FIELDS = ("disk_limit", "gcs_enabled", "gcs_limit", "min_migrate_pop",
                "link_bw", "link_slots", "link_latency", "link_mode",
                "sizes", "pop", "job_fid", "job_submit_tick",
                "job_submit_time", "job_tail", "jobs_per_tick")


def simulate_packed(grid: "PackedGrid", lane_chunk: Optional[int] = None,
                    devices: Optional[Sequence] = None,
                    record_series=None, shard: bool = False):
    """Run a packed grid on device; returns the raw per-lane aggregate dict
    (numpy arrays, lane-leading).

    ``lane_chunk`` bounds device memory: lanes execute in fixed-size
    chunks (the last chunk padded by replicating its final lane; padded
    results are discarded), every chunk reusing one compiled program.
    Per-lane results are bitwise identical to the unchunked path — lanes
    never interact. ``devices`` (default: all local devices) receives the
    chunks round-robin when more than one is present.

    ``record_series`` (``True`` = sample every tick, an int = sample
    stride in ticks, default off) adds the end-of-tick series buffers to
    the result — ``ser_disk``/``ser_queue``/``ser_run`` ``[L, T_sample,
    S]``, ``ser_gcs`` ``[L, T_sample]``, ``ser_link`` ``[L, T_sample,
    S, 3]`` — at O(T_sample * S) device memory per lane; convert with
    ``series_from_capture``. Capture off traces the exact pre-capture
    program, so those results stay bitwise identical.

    ``shard=True`` replaces the per-chunk Python loop's device
    round-robin with **one** ``shard_map`` program over a ``"lanes"``
    device mesh (``repro.parallel.sharding.lane_mesh`` over all local
    devices): the lane batch is padded to a multiple of the mesh size
    (replicating the last lane) and each device runs its slice of the
    same vmapped program — no collectives, so per-lane results stay
    bitwise identical to the unsharded path. ``lane_chunk`` still
    bounds memory (each chunk runs sharded, its size rounded up to a
    mesh multiple); ``devices=`` is the round-robin path's knob and is
    rejected together with ``shard``.
    """
    record = _normalize_record(record_series, grid.n_ticks)
    if lane_chunk is not None and lane_chunk <= 0:
        raise ValueError(f"lane_chunk must be > 0, got {lane_chunk!r}")
    if shard and devices is not None:
        raise ValueError("shard=True builds a lane mesh over the local "
                         "devices; devices= applies to the round-robin "
                         "path only")
    devices = list(devices) if devices is not None else jax.local_devices()
    if not devices:
        raise ValueError("devices must be a non-empty sequence")
    L = grid.n_lanes
    n_shards = len(devices) if shard else 0
    if not shard and lane_chunk is None and len(devices) > 1:
        lane_chunk = -(-L // len(devices))  # spread one chunk per device

    tracer = get_tracer()
    S, K = len(grid.site_names), grid.max_jobs_per_tick
    if n_shards:
        program = _shard_program(S, K, grid.n_months, record, n_shards)
    else:
        program = _grid_program(S, K, grid.n_months, "jnp", record)
    T = grid.n_ticks
    shared = (np.asarray(grid.times), np.asarray(grid.dts),
              np.asarray(grid.month_idx), np.arange(T, dtype=np.int32),
              np.float32(grid.horizon))
    lanes = [np.asarray(getattr(grid, name)) for name in _LANE_FIELDS]

    def pad_lanes(chunk, n, C):
        """Pad a ``n``-lane slice to ``C`` by replicating its last lane
        (padded results are discarded; lanes never interact)."""
        if n >= C:
            return chunk
        return [np.concatenate([a] + [a[-1:]] * (C - n), axis=0)
                for a in chunk]

    if lane_chunk is None or lane_chunk >= L:
        C = -(-L // n_shards) * n_shards if n_shards else L
        with tracer.span("simulate_packed", lanes=L, ticks=T, chunks=1,
                         shards=n_shards):
            out = program(*shared, *pad_lanes(lanes, L, C))
            return {k: np.asarray(v)[:L] for k, v in out.items()}

    C = int(lane_chunk)
    if n_shards:
        C = -(-C // n_shards) * n_shards  # each chunk shards evenly
    chunk_outs = []
    for ci, start in enumerate(range(0, L, C)):
        stop = min(start + C, L)
        chunk = pad_lanes([a[start:stop] for a in lanes], stop - start, C)
        dev = devices[ci % len(devices)]
        with tracer.span("simulate_packed.chunk", chunk=ci,
                         lanes=stop - start, shards=n_shards):
            if len(devices) > 1 and not n_shards:
                # commit every argument so each chunk dispatches (and can
                # execute concurrently) on its own device
                args = [jax.device_put(a, dev)
                        for a in (*shared, *chunk)]
                chunk_outs.append(program(*args))
            else:
                chunk_outs.append(program(*shared, *chunk))
    out = {k: np.concatenate([np.asarray(o[k]) for o in chunk_outs],
                             axis=0)[:L]
           for k in chunk_outs[0]}
    return out


def _lane_result(grid: "PackedGrid", out: dict, si: int,
                 wall_s: float, lane_base: int = 0) -> ScenarioResult:
    """Fold one spec's dynamics-lane aggregates into a ``ScenarioResult``
    with the same metric keys the event-driven ``HCDCScenario.metrics``
    emits. Several specs may share one simulated lane (pricing-only
    variants); each is billed with its own cost model.

    ``lane_base`` shifts the lane index when ``out`` holds only a chunk
    of the grid's lanes (the resilient lane-chunk job path journals
    results per chunk, before the full arrays exist)."""
    spec = grid.specs[si]
    li = int(grid.lane_of[si]) - lane_base
    names = grid.site_names
    jobs_done_site = out["jobs_done_site"][li]
    m = {
        "jobs_done": float(jobs_done_site.sum()),
        "jobs_submitted": float(grid.n_jobs[li].sum()),
        "download_pb": float(out["download_b"][li].sum()) / 1e15,
        "gcs_to_disk_pb": float(out["gcsdisk_b"][li].sum()) / 1e15,
        "disk_to_gcs_pb": float(out["diskgcs_b"][li].sum()) / 1e15,
        "gcs_used_pb": float(out["gcs_used"][li]) / 1e15,
        "job_waiting_h_mean": (float(out["wait_h_sum"][li])
                               / max(float(out["wait_n"][li]), 1.0)),
    }
    for s, name in enumerate(names):
        m[f"{name}.tape_to_disk_pb"] = float(out["tape_b"][li, s]) / 1e15
        m[f"{name}.jobs_done"] = float(jobs_done_site[s])
        m[f"{name}.disk_used_pb"] = float(out["disk_used"][li, s]) / 1e15
    bills = bills_from_monthly_totals(
        grid.cost_models[si], out["gbsec_mo"][li], out["egress_mo"][li],
        out["cls_a_mo"][li], out["cls_b_mo"][li], grid.full_months)
    for i, bill in enumerate(bills):
        m[f"month{i+1}.storage_usd"] = bill.storage_usd
        m[f"month{i+1}.network_usd"] = bill.network_usd
    # Raw monthly billing inputs (pricing-independent): exact float()
    # images of the device aggregates, so re-billing them through
    # ``bills_from_monthly_totals`` — the result cache's serve path —
    # reproduces the bills above bit-exactly under any cost model.
    monthly = {
        "gb_seconds": [float(x) for x in out["gbsec_mo"][li]],
        "egress_bytes": [float(x) for x in out["egress_mo"][li]],
        "class_a": [float(x) for x in out["cls_a_mo"][li]],
        "class_b": [float(x) for x in out["cls_b_mo"][li]],
        "full_months": int(grid.full_months),
    }
    first_refusal_s = float(out["gcs_first_refusal_s"][li])
    counters = {
        "gcs_gate_passes": int(out["gcs_gate_passes"][li]),
        "gcs_refused_ticks": int(out["gcs_refused_ticks"][li]),
        "gcs_first_refusal_h": (first_refusal_s / 3600.0
                                if np.isfinite(first_refusal_s) else None),
    }
    return ScenarioResult(
        spec=spec,
        metrics=m,
        storage_usd=sum(b.storage_usd for b in bills),
        network_usd=sum(b.network_usd for b in bills),
        ops_usd=sum(b.ops_usd for b in bills),
        wall_s=wall_s,
        events=grid.n_ticks,
        monthly=monthly,
        counters=counters,
    )


def series_from_capture(grid: "PackedGrid", out: Dict[str, np.ndarray],
                        si: int, record_series) -> Dict[str, "TimeSeries"]:
    """Convert one spec's on-device series buffers to ``TimeSeries``.

    ``out`` must come from a ``simulate_packed(..., record_series=...)``
    call with the *same* ``record_series`` value. Names match the event
    engine's ``OutputCollector`` where both backends record the
    observable — ``"{site}.disk_used"``, ``"gcs_used"``,
    ``"{site}.running_jobs"`` — plus JAX-only series:
    ``"{site}.wait_queue"`` (distinct files with waiting jobs) and
    ``"{site}.link_active.{tape_to_disk,gcs_to_disk,disk_to_gcs}"``
    (transfer slots active on each link type).
    """
    record = _normalize_record(record_series, grid.n_ticks)
    if record is None:
        raise ValueError(
            "series_from_capture requires the record_series value the "
            f"grid was simulated with, got {record_series!r}")
    if "ser_disk" not in out:
        raise KeyError(
            "no series buffers in this result — was simulate_packed "
            "called with record_series on?")
    stride, _ = record
    li = int(grid.lane_of[si])
    times = [float(t) for t in np.asarray(grid.times)[::stride]]

    series: Dict[str, TimeSeries] = {}

    def add(name: str, values: np.ndarray) -> None:
        series[name] = TimeSeries(name, times=list(times),
                                  values=[float(v) for v in values])

    add("gcs_used", out["ser_gcs"][li])
    for s, name in enumerate(grid.site_names):
        add(f"{name}.disk_used", out["ser_disk"][li, :, s])
        add(f"{name}.running_jobs", out["ser_run"][li, :, s])
        add(f"{name}.wait_queue", out["ser_queue"][li, :, s])
        for k, link in enumerate(LINK_TYPES):
            add(f"{name}.link_active.{link}", out["ser_link"][li, :, s, k])
    return series


#: Default lane-chunk size for the resilient job path when the caller
#: did not pick one: small enough that an abandoned job loses little
#: work, large enough that per-chunk dispatch overhead stays trivial.
_RESILIENT_LANE_CHUNK = 8

#: Default lane-chunk size on the worker fleet: each chunk pays a frame
#: round trip, so fleet chunks are bigger than the in-process resilient
#: default (a lost chunk still re-runs in seconds).
_FLEET_LANE_CHUNK = 64


def lane_chunk_runner(ctx: Dict) -> Callable:
    """Build the worker-side runner for lane-chunk job payloads.

    ``ctx`` is the fleet init context built by ``_simulate_packed_jobs``:
    static shapes (``S``/``K``/``n_months``), the normalized
    series-capture config, the shard count (0 =
    unsharded), and the 5 shared tick-grid arrays — shipped once at
    init, never per job. Each payload is ``{"chunk": [...15 lane
    arrays...], "n": valid_lanes}``, already padded to the program's
    chunk size by the dispatcher; the runner executes the same compiled
    program the serial path uses and truncates the padding, so fleet
    results are bitwise identical to serial ones.
    """
    n_shards = int(ctx.get("shard", 0))
    S, K, n_months, record = ctx["S"], ctx["K"], ctx["n_months"], ctx["record"]
    if n_shards:
        program = _shard_program(S, K, n_months, record, n_shards)
    else:
        program = _grid_program(S, K, n_months, "jnp", record)
    shared = tuple(ctx["shared"])

    def run(payload):
        out = program(*shared, *payload["chunk"])
        return {k: np.asarray(v)[:payload["n"]] for k, v in out.items()}

    return run


def _simulate_packed_jobs(grid: "PackedGrid", *,
                          lane_chunk: Optional[int], record_series,
                          faults, retry, job_timeout,
                          journal: Optional[Callable],
                          workers: Optional[int] = None,
                          transport=None, shard: bool = False):
    """Run a packed grid as retryable lane-chunk jobs.

    Each job executes one fixed-size slice of the grid's dynamics lanes
    through the same compiled program the plain chunked path uses, so a
    converged fault-injected run is bitwise identical to a fault-free
    one (lanes never interact; see ``simulate_packed``). Completed
    chunks are journaled through ``journal`` as they land (checkpointed
    resume); abandoned chunks leave their lanes out of the stitched
    output and are reported via the returned registry.

    ``transport`` engages the worker fleet (``repro.sim.runners``): up
    to ``workers`` persistent workers each compile the chunk program
    once (the shared tick-grid arrays ship once in the init context)
    and are fed per-chunk lane slices — the grid itself never crosses
    the wire whole. ``shard`` makes every chunk execute as one
    ``shard_map`` program over the local-device lane mesh (composable
    with the fleet: the flag rides the init context, so each worker
    shards over *its* local devices).

    Returns ``(out, registry, missing_lanes)`` where ``out`` has the
    ``simulate_packed`` shape (zero-filled for missing lanes — callers
    must skip those via ``missing_lanes``).
    """
    from repro.sim import jobs as joblib

    record = _normalize_record(record_series, grid.n_ticks)
    if lane_chunk is not None and lane_chunk <= 0:
        raise ValueError(f"lane_chunk must be > 0, got {lane_chunk!r}")
    L = grid.n_lanes
    if lane_chunk is not None:
        C = int(lane_chunk)
    else:
        C = min(L, _FLEET_LANE_CHUNK if transport is not None
                else _RESILIENT_LANE_CHUNK)
    n_shards = len(jax.local_devices()) if shard else 0
    if n_shards:
        C = -(-C // n_shards) * n_shards  # chunks shard evenly
    S, K = len(grid.site_names), grid.max_jobs_per_tick
    T = grid.n_ticks
    shared = (np.asarray(grid.times), np.asarray(grid.dts),
              np.asarray(grid.month_idx), np.arange(T, dtype=np.int32),
              np.float32(grid.horizon))
    lanes = [np.asarray(getattr(grid, name)) for name in _LANE_FIELDS]

    spec_of_chunk: Dict[tuple, list] = {}
    jobs_list = []
    for start in range(0, L, C):
        stop = min(start + C, L)
        sis = [si for si in range(grid.n_specs)
               if start <= int(grid.lane_of[si]) < stop]
        labels = tuple(grid.specs[si].label for si in sis)
        jobs_list.append(joblib.Job(job_id=f"lanes{start:05d}",
                                    payload=(start, stop), labels=labels,
                                    timeout_s=job_timeout))
        spec_of_chunk[(start, stop)] = sis

    tracer = get_tracer()

    def slice_chunk(start: int, stop: int):
        chunk = [a[start:stop] for a in lanes]
        if stop - start < C:  # pad by replicating the last real lane
            pad = C - (stop - start)
            chunk = [np.concatenate([a] + [a[-1:]] * pad, axis=0)
                     for a in chunk]
        return chunk

    on_done = None
    if journal is not None:
        def on_done(job, out_chunk):
            start, stop = job.payload
            journal([(grid.specs[si],
                      _lane_result(grid, out_chunk, si, 0.0,
                                   lane_base=start))
                     for si in spec_of_chunk[(start, stop)]])

    policy = retry if retry is not None else joblib.RetryPolicy()
    if transport is not None:
        from repro.sim.runners import run_fleet_jobs

        ctx = {"kind": "lanes", "record": record,
               "S": S, "K": K, "n_months": grid.n_months,
               "shard": n_shards, "shared": list(shared)}

        def prepare(job):
            start, stop = job.payload
            return {"chunk": slice_chunk(start, stop), "n": stop - start}

        with tracer.span("simulate_packed.fleet", lanes=L, chunk=C,
                         workers=workers or 1):
            chunk_results, registry = run_fleet_jobs(
                jobs_list, workers=workers or 1, transport=transport,
                ctx=ctx, prepare=prepare, policy=policy, faults=faults,
                on_done=on_done)
    else:
        runner = lane_chunk_runner(
            {"kind": "lanes", "record": record,
             "S": S, "K": K, "n_months": grid.n_months,
             "shard": n_shards, "shared": list(shared)})

        def run_one(job):
            start, stop = job.payload
            with tracer.span("simulate_packed.chunk", chunk=job.job_id,
                             lanes=stop - start):
                return runner({"chunk": slice_chunk(start, stop),
                               "n": stop - start})

        chunk_results, registry = joblib.run_local_jobs(
            jobs_list, run_one, policy=policy, faults=faults,
            on_done=on_done)

    out: Dict[str, np.ndarray] = {}
    done_lanes: set = set()
    for job in registry.jobs.values():
        if job.state != joblib.DONE:
            continue
        start, stop = job.payload
        o = chunk_results[job.job_id]
        if not out:
            out = {k: np.zeros((L,) + v.shape[1:], dtype=v.dtype)
                   for k, v in o.items()}
        for k, v in o.items():
            out[k][start:stop] = v
        done_lanes.update(range(start, stop))
    return out, registry, set(range(L)) - done_lanes


def run_sweep_jax(specs: Sequence["ScenarioSpec"], tick: float = 10.0,
                  progress: Optional[Callable] = None,
                  lane_chunk: Optional[int] = None,
                  devices: Optional[Sequence] = None,
                  record_series=None,
                  retry=None, faults=None,
                  job_timeout: Optional[float] = None,
                  journal: Optional[Callable] = None,
                  workers: Optional[int] = None,
                  transport=None, shard: bool = False) -> SweepResult:
    """Execute a spec grid as one batched on-device program.

    Returns a ``SweepResult`` interchangeable with the process backend's
    (``events`` reports simulation ticks instead of event-loop pops, and
    per-config ``wall_s`` is the batch wall time split evenly). Specs that
    differ only in pricing (egress option, storage price) share one
    simulated dynamics lane and are billed separately.

    ``tick`` is the clock-step duration in seconds.

    ``lane_chunk``/``devices``: see ``simulate_packed`` — bounded-memory
    chunked execution with optional multi-device round-robin.
    ``record_series`` turns on per-tick series capture (``True`` or a
    sample stride in ticks); each result then carries the same summary
    digests in ``.series`` that the process backend reports.

    ``retry``/``faults``/``job_timeout``/``journal`` engage the
    fault-tolerant lane-chunk job path (``_simulate_packed_jobs``):
    lanes execute as retryable chunk jobs, completions checkpoint
    through ``journal``, and chunks that exhaust their retries drop
    their specs from the (partial) result, reported in
    ``SweepResult.failures``. The plain path is untouched when none of
    ``retry``/``faults``/``transport`` is given. Multi-device
    round-robin is not combined with the job path.

    ``transport``/``workers`` drain the lane-chunk jobs through the
    persistent worker fleet (``repro.sim.runners``; the job path
    engages automatically). ``shard=True`` runs the lane axis as one
    ``shard_map`` program over the local-device lane mesh on whichever
    path executes (see ``simulate_packed``); both knobs preserve
    bitwise per-lane results.
    """
    from repro.core.scenarios import pack_specs

    resilient = (retry is not None or faults is not None
                 or transport is not None)
    if resilient and devices is not None:
        raise ValueError("devices round-robin is not supported on the "
                         "resilient job path (retry/faults/transport)")
    tracer = get_tracer()
    t0 = time.perf_counter()
    with tracer.span("pack_specs", n_specs=len(specs)):
        grid = pack_specs(specs, tick=tick)
    registry = None
    missing: set = set()
    if resilient:
        out, registry, missing = _simulate_packed_jobs(
            grid, lane_chunk=lane_chunk,
            record_series=record_series, faults=faults, retry=retry,
            job_timeout=job_timeout, journal=journal,
            workers=workers, transport=transport, shard=shard)
    else:
        out = simulate_packed(grid, lane_chunk=lane_chunk, devices=devices,
                              record_series=record_series, shard=shard)
    wall = time.perf_counter() - t0
    reg = get_registry()
    reg.inc("sweep.jax.runs", help="Batched JAX sweep invocations")
    reg.inc("sweep.jax.lanes", grid.n_lanes - len(missing),
            help="Dynamics lanes simulated on device")
    reg.observe("sweep.jax.wall_s", wall,
                help="Batched JAX sweep wall time (s)")
    reg.inc("sweep.jax.gcs_gate_passes",
            int(np.sum(out["gcs_gate_passes"])) if out else 0,
            help="Passes of the cloud admission gate over every lane's "
                 "ticks")
    capture = _normalize_record(record_series, grid.n_ticks) is not None
    ok_sis = [si for si in range(grid.n_specs)
              if int(grid.lane_of[si]) not in missing]
    results: List[ScenarioResult] = []
    with tracer.span("fold_results", specs=len(ok_sis)):
        for si in ok_sis:
            r = _lane_result(grid, out, si, wall / max(len(ok_sis), 1))
            if capture:
                r.series = {name: ts.summary() for name, ts in
                            series_from_capture(grid, out, si,
                                                record_series).items()}
            results.append(r)
            if progress is not None:
                progress(len(results), len(ok_sis), results[-1])
    return SweepResult(results=results, wall_s=wall,
                       failures=registry.failures() if registry else [])
