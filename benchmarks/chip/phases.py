#!/usr/bin/env python3
"""Device time of the tick by phase, from a profiler trace and the HLO.

The program names each phase of its tick with ``jax.named_scope``
(``repro.sim.batched.TICK_SCOPES``: ``tick.transfer``, ``tick.migrate``,
``tick.submit``, ``tick.waitq``, ``tick.apply``), and the scope reaches
the compiled HLO as a segment of each instruction's ``op_name``. A
profiler trace names device operations by HLO instruction, so the
compiled program's text maps each traced operation to its phase:

* :func:`hlo_scopes` maps every instruction of a compiled module's text
  to a ``tick.*`` scope, or to ``unscoped``;
* :func:`read_modules` reads, per TPU, when each compiled module ran;
* :func:`phase_times` sums device self time (``trace_reduce.self_times``)
  per scope over the window of ``bench.request`` annotations.

Run as a script on the chip, it measures a cell's window by phase, or
records a small traced window for the tests (``--record``), or compares
a window with the repository tracer on against one with it off
(``--tracer-cost``)::

    python3 benchmarks/chip/phases.py --workload cfgIII-1M.steady \\
        --seed 7 --requests 2
    python3 benchmarks/chip/phases.py --workload cfgIII-1M.steady \\
        --seed 7 --requests 2 --files 20000 --days 0.02 \\
        --record benchmarks/chip/testdata --name f20k
    python3 benchmarks/chip/phases.py --workload cfgII-1M.steady \\
        --seed 7 --requests 2 --tracer-cost 3

Each prints one JSON line. There is no CPU path: without a TPU it
exits 1.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
import shutil
import sys
import tempfile
import time
from collections import deque
from contextlib import nullcontext
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

SCOPE_PREFIX = "tick."
UNSCOPED = "unscoped"
MODULE_LINE = "XLA Modules"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_OPEN, _CLOSE = "([{", ")]}"
#: Opcodes whose metadata names no phase: XLA shares one constant among
#: all its uses and keeps the metadata of whichever it met first.
_SHARED = ("constant", "parameter")


def _balanced_end(text: str, start: int) -> int:
    """Index just past the bracket group that opens at ``text[start]``."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c in _OPEN:
            depth += 1
        elif c in _CLOSE:
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


class Instr(NamedTuple):
    """One instruction of a compiled module's text."""
    name: str
    shape: str
    opcode: str
    operands: List[str]
    calls: Optional[str]  # the computation it calls (a fusion's)
    scope: Optional[str]  # the ``tick.*`` segment of its ``op_name``


def _parse_instruction(name: str, rest: str) -> Instr:
    """The instruction ``name = rest``: the type (a shape with its
    layout, or a tuple), the opcode, the operand list, the attributes."""
    i = 0
    if rest.startswith("("):
        i = _balanced_end(rest, 0)
    else:
        depth = 0
        while i < len(rest) and not (rest[i] == " " and depth == 0):
            depth += rest[i] in _OPEN
            depth -= rest[i] in _CLOSE
            i += 1
    paren = rest.find("(", i)
    if paren < 0:
        return Instr(name, rest[:i], "", [], None, None)
    end = _balanced_end(rest, paren)
    opcode = rest[i:paren].strip()
    calls = _CALLS.search(rest, end)
    op_name = _OP_NAME.search(rest, end)
    scope = None
    if op_name and opcode not in _SHARED:
        scope = next((seg for seg in op_name.group(1).split("/")
                      if seg.startswith(SCOPE_PREFIX)), None)
    return Instr(name, rest[:i], opcode, _REF.findall(rest[paren:end]),
                 calls.group(1) if calls else None, scope)


def parse_hlo(text: str) -> Tuple[Dict[str, List[Instr]], Dict[str, str]]:
    """``(computations, roots)`` of a compiled module's text
    (``Compiled.as_text()``): each computation's instructions in the
    order the text lists them, and each computation's root."""
    comps: Dict[str, List[Instr]] = {}
    roots: Dict[str, str] = {}
    current = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
                comps[current] = []
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            comps[current].append(_parse_instruction(m.group(2), m.group(3)))
            if m.group(1):
                roots[current] = m.group(2)
    return comps, roots


def hlo_scopes(compiled_text: str) -> Dict[str, str]:
    """Map each HLO instruction of a compiled module's text
    (``Compiled.as_text()``) to its ``tick.*`` scope, or ``unscoped``.

    The rule, applied to the instructions of every computation that no
    ``calls=`` attribute names (the entry, loop bodies and conditions:
    the operations a trace shows):

    1. Own scope: the first ``tick.*`` segment of the instruction's
       ``op_name`` (never for a constant or a parameter, whose metadata
       is that of whichever use XLA kept). For an instruction that
       calls a computation (a fusion) and has none, the first one found
       in that computation by a breadth-first walk from its root over
       operands, where the computation's parameters are the caller's
       operands, which step 3 walks.
    2. Otherwise, the own scope of the first instruction reached by a
       breadth-first walk over users, within the computation.
    3. Otherwise, the same walk over operands.
    4. Otherwise ``unscoped``.

    Each walk visits neighbours in a fixed order (users in the order the
    computation lists them, operands in operand order) and stops at the
    first instruction with an own scope, so the map is deterministic.
    XLA's ``cumsum`` lowering, shared across call sites, leaves its
    ``reduce-window`` ops and the copies around them without the caller's
    scope; steps 2 and 3 give them the phase that uses or feeds them. An
    instruction inside a called computation takes its caller's scope.
    """
    comps, roots = parse_hlo(compiled_text)
    instr = {i.name: i for body in comps.values() for i in body}
    called = {i.calls for i in instr.values() if i.calls}
    own_cache: Dict[str, Optional[str]] = {}

    def own(name: str) -> Optional[str]:
        if name in own_cache:
            return own_cache[name]
        own_cache[name] = None  # guards a cycle
        calls, scope = instr[name].calls, instr[name].scope
        if scope is None and comps.get(calls):
            root = roots.get(calls, comps[calls][-1].name)
            scope = own(root) or walk(root, lambda n: instr[n].operands)
        own_cache[name] = scope
        return scope

    def walk(start: str, neighbours) -> Optional[str]:
        seen = {start}
        queue = deque([start])
        while queue:
            for nb in neighbours(queue.popleft()):
                if nb in seen or nb not in instr:
                    continue
                seen.add(nb)
                scope = own(nb)
                if scope is not None:
                    return scope
                queue.append(nb)
        return None

    out: Dict[str, str] = {}
    for comp, body in comps.items():
        if comp in called:
            continue
        users: Dict[str, List[str]] = {}
        for i in body:
            for op in i.operands:
                users.setdefault(op, []).append(i.name)
        for i in body:
            scope = (own(i.name)
                     or walk(i.name, lambda n: users.get(n, ()))
                     or walk(i.name, lambda n: instr[n].operands))
            out[i.name] = scope or UNSCOPED
    # instructions of called computations take their caller's scope
    pending = [(i.calls, out[i.name]) for i in instr.values()
               if i.calls and i.name in out]
    while pending:
        comp, scope = pending.pop()
        for i in comps.get(comp, ()):
            if i.name not in out:
                out[i.name] = scope
                if i.calls:
                    pending.append((i.calls, scope))
    return out


def read_modules(path: str) -> Dict[str, List[Tuple[int, int, str]]]:
    """Per TPU plane, the ``(start_ns, end_ns, module)`` runs of its
    ``XLA Modules`` line (``jit_lane_sim(<program id>)``)."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            runs = out.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    runs.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
    return out


def read_annotations(path: str, names: Sequence[str]
                     ) -> Dict[str, List[Tuple[int, int]]]:
    """The host planes' events of the given names (the repository
    tracer's spans, as profiler annotations), on the profiler's clock."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Tuple[int, int]]] = {n: [] for n in names}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in out:
                        out[e.name].append((e.start_ns,
                                            e.start_ns + e.duration_ns))
    return {n: sorted(v) for n, v in out.items()}


def _match(op_names, programs):
    """The op→scope map that names the most of a module's operations
    (the first such map on a tie), or ``None`` if none names any."""
    best, hits = None, 0
    for scopes in programs:
        n = sum(op in scopes for op in op_names)
        if n > hits:
            best, hits = scopes, n
    return best


def phase_times(requests: Sequence[Tuple[int, int]],
                devices: Dict[str, Sequence[Tuple[int, int, str]]],
                modules: Dict[str, Sequence[Tuple[int, int, str]]],
                programs: Sequence[Dict[str, str]]
                ) -> Optional[Dict[str, float]]:
    """Device self time per scope over the requests' window, in seconds,
    averaged over the chips.

    ``requests`` and ``devices`` are ``trace_reduce.read_trace``'s;
    ``modules`` is :func:`read_modules`'s; ``programs`` holds an
    :func:`hlo_scopes` map for each program that may have run (one per
    job-window bucket). The window's operations (those that start in it,
    as ``trace_reduce.reduce`` takes them) are grouped by the module run
    that holds them; each module name takes the map that names most of
    its operations, and each group's self times are summed by scope.
    An operation outside any module run, or of a module no map names,
    is ``unscoped``. Returns ``None`` when the trace holds no request or
    no device op, or when no map holds a ``tick.*`` scope (a program
    that names no phases).
    """
    programs = [p for p in programs
                if any(s != UNSCOPED for s in p.values())]
    if not requests or not any(devices.values()) or not programs:
        return None
    lo, hi = requests[0][0], max(e for _, e in requests)
    totals: Dict[str, int] = {}
    for plane, ops in sorted(devices.items()):
        runs = sorted(modules.get(plane, ()))
        starts = [r[0] for r in runs]
        groups: Dict[int, list] = {}
        for op in ops:
            if not lo <= op[0] < hi:
                continue
            i = bisect.bisect_right(starts, op[0]) - 1
            if i >= 0 and op[0] >= runs[i][1]:
                i = -1
            groups.setdefault(i, []).append(op)
        seen: Dict[str, set] = {}
        for i, group in groups.items():
            if i >= 0:
                seen.setdefault(runs[i][2], set()).update(
                    o[2] for o in group)
        scopes_of = {m: _match(names, programs) for m, names in seen.items()}
        for i, group in sorted(groups.items()):
            scopes = scopes_of[runs[i][2]] if i >= 0 else None
            for op, ns in trace_reduce.self_times(group).items():
                scope = scopes.get(op, UNSCOPED) if scopes else UNSCOPED
                totals[scope] = totals.get(scope, 0) + ns
    return {s: ns / len(devices) / 1e9 for s, ns in sorted(totals.items())}


def span_offsets(spans: Sequence[Dict],
                 annotations: Dict[str, Sequence[Tuple[int, int]]],
                 offset: int) -> Dict[str, Optional[int]]:
    """Per span name, the largest distance in ns between a tracer span
    (``ts``/``dur`` in microseconds of ``perf_counter``, moved by
    ``offset``) and the profiler annotation of the same rank; ``None``
    where their counts differ."""
    out: Dict[str, Optional[int]] = {}
    for name, marks in annotations.items():
        mapped = sorted((s["ts"] * 1000 + offset,
                         (s["ts"] + s["dur"]) * 1000 + offset)
                        for s in spans if s["name"] == name)
        out[name] = None if len(mapped) != len(marks) else max(
            (max(abs(a[0] - b[0]), abs(a[1] - b[1]))
             for a, b in zip(mapped, marks)), default=0)
    return out


# ------------------------------------------------------------ on the chip
def _window(cell, seed, first_r, n, tick, annotate):
    """Run ``n`` requests from request number ``first_r``, each inside a
    ``bench.request`` annotation if ``annotate``; return their wall
    seconds and the ``perf_counter_ns`` reading taken inside the first."""
    import jax

    from run import request_seeds, request_specs
    from repro.sim.sweep import run_sweep

    n_seeds = int(cell["traffic"]["seeds_per_request"])
    perf_at_first_ns = None
    t_open = time.perf_counter()
    for r in range(first_r, first_r + n):
        specs = request_specs(cell, request_seeds(seed, r, n_seeds))
        with (jax.profiler.TraceAnnotation(trace_reduce.REQUEST)
              if annotate else nullcontext()):
            if perf_at_first_ns is None:
                perf_at_first_ns = time.perf_counter_ns()
            res = run_sweep(specs, backend="jax", tick=tick)
        if not res.ok:
            raise RuntimeError(f"request {r} failed: {res.failures}")
    return time.perf_counter() - t_open, perf_at_first_ns


def compiled_text(grid) -> str:
    """The text of ``grid``'s program from a fresh compile.

    JAX's persistent compilation cache keys a program without its debug
    information, so a program loaded from it carries the ``op_name``
    metadata of whichever compile of the same operations filled the
    entry, scopes or none. A fresh compile names its instructions as
    that one did (XLA compiles deterministically) and carries this
    program's scopes."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from run import grid_program

    program, args = grid_program(grid, cached=False)
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return program.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _record(directory, name, root, path, text, spans, perf_at_first_ns,
            lane_ticks):
    """Keep a traced window for the tests: the profile, the compiled
    text and the tracer's spans. The profile names the recording host's
    source paths; they are rewritten to a neutral one of the same
    length."""
    os.makedirs(directory, exist_ok=True)
    stem = os.path.join(directory, name)
    here = root + "/"
    fixed = "/" + "_" * (len(here) - 2) + "/"
    with open(path, "rb") as f:
        pb = f.read().replace(here.encode(), fixed.encode())
    with open(stem + ".xplane.pb", "wb") as f:
        f.write(pb)
    with gzip.open(stem + ".hlo.txt.gz", "wt") as f:
        f.write(text.replace(here, fixed))
    with open(stem + ".host.json", "w") as f:
        json.dump({"spans": spans, "perf_at_first_ns": perf_at_first_ns,
                   "lane_ticks": lane_ticks}, f, indent=1)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--files", type=int, help="files per site (cut)")
    ap.add_argument("--days", type=float, help="horizon in days (cut)")
    ap.add_argument("--record", help="directory to keep the recording in")
    ap.add_argument("--name", default="phases",
                    help="file name stem of the recording")
    ap.add_argument("--tracer-cost", type=int, default=0, metavar="PAIRS",
                    help="windows with the repository tracer off and on, "
                    "in turn, with no profiler")
    args = ap.parse_args(argv)

    from run import ROOT, load_cell, request_seeds, request_specs

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.core.scenarios import pack_specs
    from repro.obs.trace import get_tracer
    from repro.sim.compile_cache import use_compile_cache
    from repro.sim.sweep import run_sweep

    if jax.devices()[0].platform != "tpu":
        print("phases: no TPU; there is no CPU path", file=sys.stderr)
        return 1
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = load_cell(args.workload)
    cfg = dict(cell["config"])
    if args.files:
        cfg["n_files"] = args.files
    if args.days:
        cfg["days"] = args.days
    cell["config"] = cfg
    tick = float(cfg["tick_s"])
    n_seeds = int(cell["traffic"]["seeds_per_request"])
    first = request_specs(cell, request_seeds(args.seed, 0, n_seeds))
    grid = pack_specs(first, tick=tick)
    t0 = time.perf_counter()
    run_sweep(first, backend="jax", tick=tick)  # warm
    line = {"workload": args.workload, "files": cfg["n_files"],
            "days": cfg["days"], "lanes": n_seeds, "ticks": grid.n_ticks,
            "K": grid.max_jobs_per_tick,
            "warm_s": time.perf_counter() - t0}
    tracer = get_tracer()

    lane_ticks = args.requests * n_seeds * grid.n_ticks
    lane_days = args.requests * n_seeds * cfg["days"]
    if args.tracer_cost:
        windows = []
        for _ in range(args.tracer_cost):
            for on in (False, True):
                tracer.reset()
                if on:
                    tracer.enable()
                wall, _ = _window(cell, args.seed,
                                  1 + len(windows) * args.requests,
                                  args.requests, tick, annotate=False)
                tracer.disable()
                windows.append({"tracer": on, "wall_s": wall,
                                "lane_days_per_s": lane_days / wall,
                                "spans": len(tracer.events)})
        line["windows"] = windows
        print(json.dumps(line), flush=True)
        return 0

    text = compiled_text(grid)
    profile_dir = tempfile.mkdtemp(prefix="phases_profile_")
    tracer.reset()
    tracer.enable()
    jax.profiler.start_trace(profile_dir)
    _, perf_at_first_ns = _window(cell, args.seed, 1, args.requests, tick,
                                  annotate=True)
    jax.profiler.stop_trace()
    tracer.disable()
    spans = [{"name": e["name"], "ts": e["ts"], "dur": e["dur"]}
             for e in tracer.events if e.get("ph") == "X"]
    path = [os.path.join(d, f) for d, _, fs in os.walk(profile_dir)
            for f in fs if f.endswith(".xplane.pb")][0]
    if args.record:
        _record(args.record, args.name, ROOT, path, text, spans,
                perf_at_first_ns, lane_ticks)
    reqs, devs = trace_reduce.read_trace(path)
    modules = read_modules(path)
    scopes = hlo_scopes(text)
    reduced = trace_reduce.reduce(reqs, devs, spans, perf_at_first_ns)
    phases = phase_times(reqs, devs, modules, [scopes])
    if reduced is None or phases is None:
        print(f"phases: nothing to reduce: {len(reqs)} requests, "
              f"{sum(map(len, devs.values()))} device ops, "
              f"{sum(s != UNSCOPED for s in scopes.values())} scoped "
              "instructions", file=sys.stderr)
        return 1
    per_us = 1e6 / lane_ticks
    names = sorted({s["name"] for s in spans})
    line.update({
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "tick_device_us": reduced["busy_s"] * per_us,
        "phase_device_us": {s: v * per_us for s, v in phases.items()},
        "phase_sum_over_busy": sum(phases.values()) / reduced["busy_s"],
        "device_ops": reduced["device_ops"],
        "modules": sorted({m for runs in modules.values()
                           for _, _, m in runs}),
        "span_offset_ns": span_offsets(
            spans, read_annotations(path, names),
            reqs[0][0] - perf_at_first_ns),
    })
    shutil.rmtree(profile_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
