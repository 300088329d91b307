"""Diff a fresh benchmark JSON against the committed perf baseline.

Compares the ``derived`` column (throughput: higher is better) of selected
rows by name prefix and fails when any regresses by more than the allowed
fraction. Row names embed grid sizes (``sweep.jax.warm.216cfg8lane``), so
matching is by prefix; a prefix present in only one file is reported and
skipped (grid shapes legitimately change across PRs).

Baselines are only comparable at the same scale: if the two files disagree
on the ``fast`` flag (smoke vs full benchmark scale), the check exits with
``EXIT_SCALE_MISMATCH`` and an actionable message — a mis-scaled committed
baseline would otherwise permanently self-disable the gate. Regenerate the
committed baseline with ``make bench-baseline`` (FAST scale, matching CI's
bench-smoke job).

Exit codes (CI distinguishes "skipped" from "passed"/"failed"):

- 0 ``EXIT_OK``              — all compared rows within tolerance
- 1 ``EXIT_REGRESSION``      — at least one row regressed
- 3 ``EXIT_SCALE_MISMATCH``  — baseline/current ``fast`` flags differ
- 4 ``EXIT_NO_BASELINE``     — baseline file absent/unreadable
- 5 ``EXIT_NO_CURRENT``      — fresh results file absent/unreadable

When ``--summary PATH`` is given (or ``$GITHUB_STEP_SUMMARY`` is set, as
on GitHub Actions), a markdown table of the compared rows, their deltas,
and pass/fail is appended there, so the perf trajectory is readable on the
workflow run page without downloading artifacts. ``--baseline -`` skips
the comparison entirely and just tabulates the current file (the nightly
full-scale run, which has no committed full-scale baseline).

Usage (the CI bench-smoke job and ``make bench-smoke`` run this)::

    python scripts/check_bench_regression.py BENCH_4.json BENCH_ci.json \
        [--rows sweep.jax.warm sweep.jax.lanes_per_sec] [--max-regression 0.3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

EXIT_OK = 0
EXIT_REGRESSION = 1
EXIT_SCALE_MISMATCH = 3
EXIT_NO_BASELINE = 4
EXIT_NO_CURRENT = 5  # the fresh results file itself is absent/unreadable

#: Rows that gate CI (prefix match). Throughput of the batched backend is
#: the perf trajectory this repo tracks (ISSUE 4 acceptance); the decide
#: rows track the decision layer's lane efficiency (ISSUE 5).
DEFAULT_ROWS = ("sweep.jax.warm", "sweep.jax.lanes_per_sec")


def _find(doc: dict, prefix: str):
    rows = [b for b in doc.get("benches", [])
            if b["name"] == prefix or b["name"].startswith(prefix + ".")]
    return rows[0] if rows else None


def _write_summary(path: str, lines: List[str]) -> None:
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fail on benchmark throughput regression vs baseline")
    ap.add_argument("baseline",
                    help="committed baseline JSON (BENCH_4.json); '-' "
                         "tabulates the current file without comparing")
    ap.add_argument("current", help="freshly produced JSON (BENCH_ci.json)")
    ap.add_argument("--rows", nargs="+", default=list(DEFAULT_ROWS),
                    help="row-name prefixes to compare (derived column)")
    ap.add_argument("--max-regression", type=float, default=0.30,
                    help="allowed fractional drop in derived throughput "
                         "(default 0.30)")
    ap.add_argument("--summary", default=os.environ.get(
                        "GITHUB_STEP_SUMMARY", ""),
                    help="append a markdown result table to this file "
                         "(default: $GITHUB_STEP_SUMMARY when set)")
    args = ap.parse_args(argv)

    try:
        with open(args.current) as f:
            cur = json.load(f)
    except OSError as e:
        # e.g. the bench run crashed before writing its JSON; a clean code
        # (not a traceback's generic 1 == EXIT_REGRESSION) keeps the CI
        # outcome classification honest
        print(f"bench-diff: no current results ({e})", file=sys.stderr)
        return EXIT_NO_CURRENT

    md: List[str] = ["### Benchmark regression check", ""]

    if args.baseline == "-":
        md += ["_No baseline comparison (table-only mode)._", "",
               "| row | derived |", "|---|---|"]
        for prefix in args.rows:
            c = _find(cur, prefix)
            val = f"{float(c['derived']):.4g}" if c else "missing"
            name = c["name"] if c else prefix
            print(f"bench-diff: {name}: {val}")
            md.append(f"| `{name}` | {val} |")
        if args.summary:
            _write_summary(args.summary, md)
        return EXIT_OK

    base: Optional[dict] = None
    try:
        with open(args.baseline) as f:
            base = json.load(f)
    except OSError as e:
        print(f"bench-diff: no baseline ({e}); skipping", file=sys.stderr)
        if args.summary:
            _write_summary(args.summary, md + [
                f"_Skipped: baseline `{args.baseline}` missing._"])
        return EXIT_NO_BASELINE

    if base.get("fast") != cur.get("fast"):
        print(f"bench-diff: scale mismatch (baseline fast={base.get('fast')}"
              f", current fast={cur.get('fast')}) — the committed baseline "
              "must match the comparison scale; regenerate it with "
              "`make bench-baseline`", file=sys.stderr)
        if args.summary:
            _write_summary(args.summary, md + [
                f"_Scale mismatch: baseline fast={base.get('fast')}, "
                f"current fast={cur.get('fast')} — regenerate with "
                "`make bench-baseline`._"])
        return EXIT_SCALE_MISMATCH

    md += [f"Tolerance: {args.max_regression:.0%} drop in `derived` "
           "(throughput).", "",
           "| row | baseline | current | delta | status |",
           "|---|---|---|---|---|"]
    failures = []
    for prefix in args.rows:
        b, c = _find(base, prefix), _find(cur, prefix)
        if b is None or c is None:
            where = "baseline" if b is None else "current"
            print(f"bench-diff: {prefix}: missing in {where}; skipped")
            md.append(f"| `{prefix}` | — | — | — | skipped "
                      f"(missing in {where}) |")
            continue
        old, new = float(b["derived"]), float(c["derived"])
        if old <= 0:
            print(f"bench-diff: {prefix}: non-positive baseline {old}; "
                  "skipped")
            md.append(f"| `{prefix}` | {old:.4g} | {new:.4g} | — | skipped "
                      "(non-positive baseline) |")
            continue
        change = (new - old) / old
        status = "OK"
        if change < -args.max_regression:
            status = "REGRESSION"
            failures.append(prefix)
        print(f"bench-diff: {prefix}: {old:.4g} -> {new:.4g} "
              f"({change:+.1%}) {status}")
        icon = "✅" if status == "OK" else "❌"
        md.append(f"| `{b['name']}` | {old:.4g} | {new:.4g} | "
                  f"{change:+.1%} | {icon} {status} |")
    if args.summary:
        _write_summary(args.summary, md)
    if failures:
        print(f"bench-diff: FAILED rows: {', '.join(failures)} "
              f"(allowed drop {args.max_regression:.0%})", file=sys.stderr)
        return EXIT_REGRESSION
    return EXIT_OK


if __name__ == "__main__":
    raise SystemExit(main())
