"""Plain reference for the chip benchmark: the HCDC carousel as events.

This is a self-contained copy of the event-driven engine the paper
describes (arXiv:2105.03201 §4-§5; the repository's ``backend="process"``),
written against the benchmark's own configuration files and importing
nothing of the program under test. It simulates one scenario (one seed of
one deployment under one traffic mix) on an integer-second event clock:

* every 10 s the generator, per site: hot-tier deletions and hot->cold
  migrations of files nobody consumes, the tick's job submissions (each
  job picks a file by popularity), then FIFO admission of waiting jobs
  into the disk window as space frees;
* a transfer holds one of a link's slots, waits out the source's access
  latency (tape: 30 min) and completes after ``size / throughput``;
* a job whose file is on disk downloads it and runs for an exponential
  duration;
* the cloud bucket integrates stored bytes over time and bills storage,
  tiered egress and operations per 30-day month.

The draws follow the program's event engine draw for draw: catalogue
sizes then popularity per site, the per-tick job-count stream, then one
uniform per submitted job (file choice) and one exponential per job that
starts (run time), in event order. The batched engine replicates the
catalogue and the arrival stream but takes per-job choices from another
continuation of the stream, so it agrees with this reference
statistically and not per draw.

``simulate(..., control=True)`` is the control: the same reference with
one guarantee of the configuration broken, the Table 4 limit of
``max_active`` concurrent transfers per link (the control opens every
transfer at once). ``compare`` turns the program's results and the
reference's into the numbers that decide ``correct``.
"""

from __future__ import annotations

import heapq
import math
from collections import deque
from typing import Dict, List, Optional, Sequence

import numpy as np

GiB = 1024.0 ** 3
TB = 1000.0 ** 4
HOUR = 3600
MONTH_SECONDS = 30 * 24 * 3600
ABSENT, IN_FLIGHT, PRESENT = 0, 1, 2


# ---------------------------------------------------------------- traffic
def schedule(workload: Dict, n_gen: int, gen_interval: float):
    """Per-generator-tick arrival multiplier and selection power.

    ``workload`` is the traffic file's ``workload`` object: ``steady``,
    ``campaign`` (square wave: ``peak`` x the base rate for the first
    ``duty`` of every ``period_h`` hours, ``off`` x after), ``diurnal``
    (``1 + amplitude * sin(2 pi (t_h - phase_h) / period_h)``) or
    ``zipf-drift`` (selection power stepping from ``power_start`` to
    ``power_end`` in ``steps`` segments). Returns ``(rate_mult, power)``,
    ``power`` being ``None`` where the base popularity power holds.
    """
    name = workload["name"]
    t_h = np.arange(n_gen, dtype=np.float64) * gen_interval / 3600.0
    if name == "steady":
        return np.ones(n_gen, dtype=np.float64), None
    if name == "campaign":
        period = workload.get("period_h", 24.0)
        phase = np.mod(t_h, period) / period
        return (np.where(phase < workload.get("duty", 0.25),
                         float(workload.get("peak", 3.0)),
                         float(workload.get("off", 0.5))), None)
    if name == "diurnal":
        mult = 1.0 + workload.get("amplitude", 0.5) * np.sin(
            2.0 * math.pi * (t_h - workload.get("phase_h", 0.0))
            / workload.get("period_h", 24.0))
        return np.maximum(mult, 0.0), None
    if name == "zipf-drift":
        start = workload.get("power_start", 3.5)
        end = workload.get("power_end", 1.5)
        steps = min(int(workload.get("steps", 8)), n_gen) if n_gen > 1 else 1
        seg = np.minimum((np.arange(n_gen) * steps) // max(n_gen, 1),
                         steps - 1).astype(np.float64)
        return (np.ones(n_gen, dtype=np.float64),
                start + (end - start) * seg / max(steps - 1, 1))
    raise ValueError(f"unknown workload {name!r}")


# ---------------------------------------------------------------- pricing
def egress_cost(pricing: Dict, egress: str, monthly_bytes: float) -> float:
    """USD for one month's bucket egress (tiered internet or peering)."""
    if egress != "internet":
        return pricing["peering_usd_per_gib"][egress] * monthly_bytes / GiB
    cost, prev, left = 0.0, 0.0, monthly_bytes
    for bound_tib, price in pricing["egress_tiers"]:
        bound = float("inf") if bound_tib is None else bound_tib * 1024.0 ** 4
        span = min(left, bound - prev)
        if span <= 0:
            break
        cost += price * span / GiB
        left -= span
        prev = bound
    return cost


def bill(pricing: Dict, egress: str, monthly: Sequence[tuple]) -> Dict:
    """Storage, network and operations USD over ``(gb_seconds,
    egress_bytes, class_a, class_b)`` month tuples."""
    out = {"storage_usd": 0.0, "network_usd": 0.0, "ops_usd": 0.0}
    for gb_s, egress_b, cls_a, cls_b in monthly:
        out["storage_usd"] += (pricing["storage_usd_per_gb_month"] * gb_s
                               / MONTH_SECONDS)
        out["network_usd"] += egress_cost(pricing, egress, egress_b)
        out["ops_usd"] += (cls_a / 1e4 * pricing["class_a_usd_per_10k"]
                           + cls_b / 1e4 * pricing["class_b_usd_per_10k"])
    return out


# ------------------------------------------------------------------ model
class _Link:
    __slots__ = ("rate", "slots", "latency", "active", "queue", "src_gcs",
                 "dst_gcs")

    def __init__(self, rate, slots, latency, src_gcs=False, dst_gcs=False):
        self.rate, self.slots, self.latency = rate, slots, latency
        self.src_gcs, self.dst_gcs = src_gcs, dst_gcs
        self.active = 0
        self.queue: deque = deque()


class _Job:
    __slots__ = ("fid", "submitted", "resolved")

    def __init__(self, fid, submitted):
        self.fid, self.submitted, self.resolved = fid, submitted, False


class _Site:
    def __init__(self, sizes, pop, cum_w, disk_limit, links):
        n = len(sizes)
        self.sizes, self.pop, self.cum_w = sizes, pop, cum_w
        self.disk_limit = disk_limit
        self.disk_used = 0.0
        self.disk_state = np.zeros(n, dtype=np.int8)
        self.gcs_state = np.zeros(n, dtype=np.int8)
        self.consumers = np.zeros(n, dtype=np.int32)
        self.waiting: deque = deque()
        self.waiting_by_fid: Dict[int, List[_Job]] = {}
        self.jobs_for_fid: Dict[int, List[_Job]] = {}
        self.deletable: set = set()
        self.acc = 0.0
        self.tape, self.gcs_in, self.gcs_out = links
        self.jobs_done = 0
        self.download_b = self.tape_b = self.gcs_disk_b = self.disk_gcs_b = 0.0


class Scenario:
    """One seed of one deployment under one traffic mix."""

    def __init__(self, config: Dict, traffic: Dict, seed: int,
                 control: bool = False):
        files, jobs, links = config["files"], config["jobs"], config["links"]
        self.horizon = int(config["days"] * 86400)
        self.gen_interval = int(jobs["gen_interval_s"])
        self.download = links["download_MB_s"] * 1e6
        self.dur_lam, self.dur_lo = (jobs["duration_lambda_per_s"],
                                     jobs["duration_lo_s"])
        gcs_limit = config["gcs_limit_tb"]
        self.gcs_on = gcs_limit is None or gcs_limit > 0
        self.gcs_limit = None if gcs_limit is None else gcs_limit * TB
        self.gcs_used = 0.0
        self.rng = rng = np.random.default_rng(seed)
        self.heap: list = []
        self.seq = 0
        self.wait_h: List[float] = []
        # bucket month integration
        self.month_start = self.last_sync = 0
        self.gb_s = self.egress_b = 0.0
        self.cls_a = self.cls_b = 0
        self.monthly: List[tuple] = []
        disk_limit = (None if config["disk_limit_tb"] is None
                      else config["disk_limit_tb"] * TB)
        lo = files["size_lo_bytes"] / GiB
        hi = files["size_hi_bytes"] / GiB
        n = config["n_files"]
        slots = math.inf if control else links["max_active"]
        self.sites = []
        for site in config["sites"]:
            sizes = np.clip(rng.exponential(1.0 / files["size_lambda_per_GiB"],
                                            size=n), lo, hi) * GiB
            pop = np.clip(rng.geometric(files["popularity_p"], n),
                          files["popularity_lo"], files["popularity_hi"] - 1)
            cum_w = np.cumsum(pop.astype(float) ** files["selection_power"])
            links_ = (
                _Link(site["tape_to_disk_MB_s"] * 1e6, slots,
                      float(links["tape_latency_s"])),
                _Link(links["gcs_to_disk_MB_s"] * 1e6, slots, 0.0,
                      src_gcs=True),
                _Link(links["disk_to_gcs_MB_s"] * 1e6, slots, 0.0,
                      dst_gcs=True))
            self.sites.append(_Site(sizes, pop, cum_w / cum_w[-1],
                                    disk_limit, links_))
        self.selection_power = files["selection_power"]
        n_gen = self.horizon // self.gen_interval + 1
        counts = np.maximum(rng.normal(jobs["per_tick_mu"],
                                       jobs["per_tick_sigma"],
                                       size=(len(self.sites), n_gen)), 0.0)
        mult, self.power = schedule(traffic["workload"], n_gen,
                                    self.gen_interval)
        self.counts = counts * mult
        self._cdf_cache: Dict[float, np.ndarray] = {}

    # -- clock
    def at(self, when: int, fn) -> None:
        heapq.heappush(self.heap, (int(when), self.seq, fn))
        self.seq += 1

    def _sync(self, now: int) -> None:
        while now - self.month_start >= MONTH_SECONDS:
            boundary = self.month_start + MONTH_SECONDS
            self.gb_s += self.gcs_used / 1e9 * (boundary - self.last_sync)
            self._close_month()
            self.last_sync = self.month_start = boundary
        self.gb_s += self.gcs_used / 1e9 * (now - self.last_sync)
        self.last_sync = now

    def _close_month(self) -> None:
        self.monthly.append((self.gb_s, self.egress_b, self.cls_a, self.cls_b))
        self.gb_s = self.egress_b = 0.0
        self.cls_a = self.cls_b = 0

    # -- transfers
    def _submit(self, link: _Link, size: float, on_done) -> None:
        if link.dst_gcs:
            self.gcs_used += size
        item = (size, on_done)
        if link.active < link.slots:
            self._start(link, item)
        else:
            link.queue.append(item)

    def _start(self, link: _Link, item) -> None:
        link.active += 1
        started = self.now + int(round(link.latency))
        done_at = started + max(1, int(round(item[0] / link.rate)))
        self.at(done_at, lambda now: self._complete(now, link, item))

    def _complete(self, now: int, link: _Link, item) -> None:
        size, on_done = item
        link.active -= 1
        if link.src_gcs:
            self._sync(now)
            self.egress_b += size
            self.cls_b += 1
        if link.dst_gcs:
            self._sync(now)
            self.cls_a += 1
        on_done(now, size)
        while link.queue and link.active < link.slots:
            self._start(link, link.queue.popleft())

    # -- jobs

    def _cdf(self, st: _Site, power: Optional[float]) -> np.ndarray:
        if power is None:
            return st.cum_w
        key = (id(st), power)
        if key not in self._cdf_cache:
            cw = np.cumsum(st.pop.astype(float) ** power)
            self._cdf_cache[key] = cw / cw[-1]
        return self._cdf_cache[key]

    def _submit_job(self, now: int, st: _Site, power) -> None:
        u = float(self.rng.random())
        fid = int(np.searchsorted(self._cdf(st, power), u, side="right"))
        job = _Job(fid, now)
        st.consumers[fid] += 1
        st.deletable.discard(fid)
        ds = st.disk_state[fid]
        if ds == PRESENT:
            self._data_ready(now, st, job)
        elif ds == IN_FLIGHT:
            st.jobs_for_fid.setdefault(fid, []).append(job)
        elif not self._start_input(now, st, job):
            st.waiting.append(job)
            st.waiting_by_fid.setdefault(fid, []).append(job)

    def _start_input(self, now: int, st: _Site, job: _Job) -> bool:
        fid = job.fid
        if st.disk_state[fid] == PRESENT:
            self._data_ready(now, st, job)
            return True
        if st.disk_state[fid] == IN_FLIGHT:
            st.jobs_for_fid.setdefault(fid, []).append(job)
            return True
        size = float(st.sizes[fid])
        if st.disk_limit is not None and st.disk_used + size > st.disk_limit:
            return False
        from_gcs = self.gcs_on and st.gcs_state[fid] == PRESENT
        st.disk_used += size
        st.disk_state[fid] = IN_FLIGHT
        st.jobs_for_fid.setdefault(fid, []).append(job)
        for w in st.waiting_by_fid.pop(fid, []):
            if not w.resolved and w is not job:
                w.resolved = True
                st.jobs_for_fid[fid].append(w)

        def done(now_, size_, st=st, fid=fid, from_gcs=from_gcs):
            st.disk_state[fid] = PRESENT
            if from_gcs:
                st.gcs_disk_b += size_
            else:
                st.tape_b += size_
            for j in st.jobs_for_fid.pop(fid, []):
                self._data_ready(now_, st, j)
            if st.consumers[fid] == 0 and st.disk_limit is not None:
                st.deletable.add(fid)

        self._submit(st.gcs_in if from_gcs else st.tape, size, done)
        return True

    def _data_ready(self, now: int, st: _Site, job: _Job) -> None:
        self.wait_h.append((now - job.submitted) / HOUR)
        size = float(st.sizes[job.fid])
        run = float(np.clip(self.rng.exponential(1.0 / self.dur_lam),
                            self.dur_lo, np.inf))
        st.download_b += size

        def finish(now_, st=st, fid=job.fid):
            st.jobs_done += 1
            st.consumers[fid] -= 1
            if (st.consumers[fid] == 0 and st.disk_state[fid] == PRESENT
                    and st.disk_limit is not None):
                st.deletable.add(fid)

        self.at(now + max(1, int(size / self.download + run)), finish)

    # -- generator phases
    def _deletions(self, st: _Site) -> None:
        if st.disk_limit is None or not st.deletable:
            return
        done_fids = []
        for fid in st.deletable:
            if st.consumers[fid] != 0 or st.disk_state[fid] != PRESENT:
                done_fids.append(fid)
                continue
            if not self.gcs_on or st.gcs_state[fid] == PRESENT:
                st.disk_used -= float(st.sizes[fid])
                st.disk_state[fid] = ABSENT
                done_fids.append(fid)
            elif st.gcs_state[fid] == ABSENT:
                size = float(st.sizes[fid])
                if (self.gcs_limit is not None
                        and self.gcs_used + size > self.gcs_limit):
                    continue  # cold tier full; retry next tick
                st.gcs_state[fid] = IN_FLIGHT

                def migrated(now_, size_, st=st, fid=fid):
                    st.gcs_state[fid] = PRESENT
                    st.disk_gcs_b += size_
                    if st.consumers[fid] == 0 and st.disk_state[fid] == PRESENT:
                        st.disk_used -= float(st.sizes[fid])
                        st.disk_state[fid] = ABSENT

                self._submit(st.gcs_out, size, migrated)
                done_fids.append(fid)
            else:
                done_fids.append(fid)  # migration already in flight
        for fid in done_fids:
            st.deletable.discard(fid)

    def _waiting(self, now: int, st: _Site) -> None:
        while st.waiting:
            job = st.waiting[0]
            if job.resolved:
                st.waiting.popleft()
                continue
            if self._start_input(now, st, job):
                st.waiting.popleft()
                job.resolved = True
            else:
                break

    def _generate(self, now: int, tick: int) -> None:
        power = None if self.power is None else float(self.power[tick])
        for i, st in enumerate(self.sites):
            self._deletions(st)
            st.acc += float(self.counts[i][tick])
            n = int(st.acc)
            st.acc -= n
            for _ in range(n):
                self._submit_job(now, st, power)
            self._waiting(now, st)
        self.at(now + self.gen_interval,
                lambda now_: self._generate(now_, tick + 1))

    def run(self) -> Dict:
        self.now = 0
        self.at(0, lambda now: self._generate(now, 0))
        heap = self.heap
        while heap and heap[0][0] <= self.horizon:
            self.now, _, fn = heapq.heappop(heap)
            fn(self.now)
        self._sync(self.horizon)
        if self.gb_s > 0 or self.egress_b > 0:
            self._close_month()
        return self.result()

    def result(self) -> Dict:
        sites = self.sites
        return {
            "jobs_done": sum(s.jobs_done for s in sites),
            "download_b": sum(s.download_b for s in sites),
            "tape_b": sum(s.tape_b for s in sites),
            "gcs_to_disk_b": sum(s.gcs_disk_b for s in sites),
            "disk_to_gcs_b": sum(s.disk_gcs_b for s in sites),
            "gcs_used_b": self.gcs_used,
            "disk_used_b": [s.disk_used for s in sites],
            "wait_h_mean": float(np.mean(self.wait_h)) if self.wait_h else 0.0,
            "monthly": list(self.monthly),
        }


def simulate(config: Dict, traffic: Dict, seed: int,
             control: bool = False) -> Dict:
    """Run one scenario of the reference; see :class:`Scenario`."""
    return Scenario(config, traffic, seed, control).run()


# ------------------------------------------------------------- comparison
def _gap(a: float, b: float) -> float:
    """|a - b| relative to the reference ``b`` (1 where only one is 0)."""
    if a == b:
        return 0.0
    return abs(a - b) / abs(b) if b else 1.0


def rebill(pricing: Dict, egress: str, monthly: Dict) -> Dict:
    """Bill a result's raw monthly totals by the program's emission rule:
    every complete month, and a trailing partial one with activity."""
    rows = [(g, e, int(round(a)), int(round(b))) for i, (g, e, a, b) in
            enumerate(zip(monthly["gb_seconds"], monthly["egress_bytes"],
                          monthly["class_a"], monthly["class_b"]))
            if i < monthly["full_months"] or g > 0 or e > 0]
    return bill(pricing, egress, rows)


def compare(pairs: Sequence[tuple], config: Dict) -> Dict[str, float]:
    """The numbers that decide ``correct``, over ``(program result,
    reference result)`` pairs of one run.

    ``*_gap``: per lane, the gap relative to the reference; the run's
    number is its worst lane's. ``disk_over``: the largest share by which
    a site's disk ends over its limit. ``cloud_bytes``: bytes the program
    moved to, from or kept in the cloud tier. ``bill_exact``: the program's
    own monthly totals re-billed with this file's pricing, against the
    dollars it reported (the host fold must agree to the last bit).
    """
    names = [s["name"] for s in config["sites"]]
    limit = (None if config["disk_limit_tb"] is None
             else config["disk_limit_tb"] * TB)
    gaps: Dict[str, List[float]] = {}
    disk_over = cloud_bytes = bill_exact = 0.0
    for prog, ref in pairs:
        m = prog.metrics
        usd = bill(config["pricing"], prog.spec.egress, ref["monthly"])
        values = {
            "jobs": (m["jobs_done"], ref["jobs_done"]),
            "download": (m["download_pb"] * 1e15, ref["download_b"]),
            "tape": (sum(m[f"{n}.tape_to_disk_pb"] for n in names) * 1e15,
                     ref["tape_b"]),
            "migrate": (m["disk_to_gcs_pb"] * 1e15, ref["disk_to_gcs_b"]),
            "recall": (m["gcs_to_disk_pb"] * 1e15, ref["gcs_to_disk_b"]),
            "wait": (m["job_waiting_h_mean"], ref["wait_h_mean"]),
            "storage": (prog.storage_usd, usd["storage_usd"]),
            "network": (prog.network_usd, usd["network_usd"]),
            "ops": (prog.ops_usd, usd["ops_usd"]),
        }
        for k, (a, b) in values.items():
            gaps.setdefault(k, []).append(_gap(a, b))
        if limit is not None:
            for n in names:
                used = m[f"{n}.disk_used_pb"] * 1e15
                disk_over = max(disk_over, used / limit - 1.0)
        cloud_bytes += 1e15 * (m["disk_to_gcs_pb"] + m["gcs_to_disk_pb"]
                               + m["gcs_used_pb"])
        mine = rebill(config["pricing"], prog.spec.egress, prog.monthly)
        for k in ("storage_usd", "network_usd", "ops_usd"):
            bill_exact = max(bill_exact, _gap(getattr(prog, k), mine[k]))
    if not pairs:
        return {}
    out = {f"{k}_gap": max(v) for k, v in gaps.items()}
    out.update(disk_over=disk_over, cloud_bytes=cloud_bytes,
               bill_exact=bill_exact)
    return out
