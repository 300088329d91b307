"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else:

* device busy time: the union of the op intervals on each TPU's
  ``XLA Ops`` line, clipped to the window, averaged over the chips;
* per-op device self time by HLO name (an op's time less that of the
  ops nested in it), averaged over the chips; the top ten go to
  ``breakdown``;
* the longest idle stretches on the first chip, each named after what
  the host was doing.

The window is taken from the host's ``bench.request`` annotations, which
the harness wraps around every request: from the first one's start to
the last one's end. Host spans of the repository's tracer (``pack_specs``,
``simulate_packed``) are on the host's ``perf_counter`` clock; the harness
gives the ``perf_counter`` reading taken just inside the first annotation,
and that annotation's start on the profiler's clock gives the offset.
Time inside a request that neither span covers is the host fold.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
REQUEST = "bench.request"


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def hlo_name(text: str) -> str:
    """``fusion.98`` from an op event's ``%fusion.98 = f32[...] ...``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_trace(path: str):
    """``(requests, devices)``: the host's request intervals and, per
    TPU plane, its ``(start_ns, end_ns, hlo_name)`` op events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    requests: List[Tuple[int, int]] = []
    devices: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                hlo_name(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                requests.extend((e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.name == REQUEST)
    return sorted(requests), devices


def self_times(ops: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Per op name, the time its events ran less the time of the events
    nested in them (a ``while`` op holds its body's ops on the same
    line), in ns."""
    out: Dict[str, int] = {}
    stack: List[list] = []  # [end, name, own]

    def close(item):
        out[item[1]] = out.get(item[1], 0) + item[2]

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    for item in stack:
        close(item)
    return out


def _activity(requests, host):
    """Label function: what the host was doing at a profiler time."""
    def at(t: int) -> str:
        inner = [(e - s, n) for s, e, n in host if s <= t < e]
        if inner:
            return min(inner)[1]
        if any(s <= t < e for s, e in requests):
            return "fold" if host else REQUEST
        return "between requests"
    return at


def reduce(requests: Sequence[Tuple[int, int]],
           devices: Dict[str, Sequence[Tuple[int, int, str]]],
           spans: Sequence[Dict] = (), perf_at_first_ns: Optional[int] = None,
           top: int = 10) -> Optional[Dict]:
    """Busy time, per-op time and idle gaps over the requests' window.

    ``spans`` are the repository tracer's events (``name``, ``ts`` and
    ``dur`` in microseconds of ``perf_counter``); ``perf_at_first_ns`` is
    the ``perf_counter_ns`` reading taken inside the first request's
    annotation. An idle gap of the first chip is cut where the host's
    activity changes, and each piece is named after that activity.
    Returns ``None`` when the trace holds no request or no device op, so
    that no metric is made from an empty trace.
    """
    if not requests or not any(devices.values()):
        return None
    lo, hi = requests[0][0], max(e for _, e in requests)
    busy = {}
    op_ns: Dict[str, int] = {}
    for name, ops in sorted(devices.items()):
        inside = [o for o in ops if lo <= o[0] < hi]
        busy[name] = _merge(_clip([(s, e) for s, e, _ in inside], lo, hi))
        for op, ns in self_times(inside).items():
            op_ns[op] = op_ns.get(op, 0) + ns
    n_dev = len(busy)
    host = []
    if perf_at_first_ns is not None:
        offset = requests[0][0] - perf_at_first_ns
        host = [(sp["ts"] * 1000 + offset,
                 (sp["ts"] + sp["dur"]) * 1000 + offset, sp["name"])
                for sp in spans]
    at = _activity(requests, host)
    cuts = sorted({t for s, e, _ in host for t in (s, e)}
                  | {t for s, e in requests for t in (s, e)})
    pieces = []
    edge = lo
    for s, e in busy[sorted(busy)[0]] + [(hi, hi)]:
        if s > edge:
            bounds = [edge] + [t for t in cuts if edge < t < s] + [s]
            for a, b in zip(bounds, bounds[1:]):
                label = at(a)
                if pieces and pieces[-1][2] == a and pieces[-1][0] == label:
                    pieces[-1] = (label, pieces[-1][1] + b - a, b)
                else:
                    pieces.append((label, b - a, b))
        edge = max(edge, e)
    pieces.sort(key=lambda p: -p[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(sum(e - s for s, e in m) for m in busy.values())
        / n_dev / 1e9,
        "device_ops": [[op, ns / n_dev / 1e9] for op, ns in
                       sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, ns / 1e9] for label, ns, _ in pieces[:top]],
    }
