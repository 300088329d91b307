#!/usr/bin/env python3
"""The control for ``correct``: the reference with a guarantee broken.

    python3 benchmarks/chip/control.py --workload cfgIII-1M.steady \\
        --seed 7 --lanes 4

The configurations state that no link carries more than ``max_active``
concurrent transfers (Table 4: 100 slots per link; the tape system is
the carousel's bottleneck). The control is the plain reference run with
that guarantee broken (every transfer starts at once), put in the
program's place: its results go through the same comparison as the
program's, at the cell's own size, against the sound reference on the
same seeds. Each number's smallest control reading is its upper reading;
a limit lies between the program's readings and that one. The benchmark's
runs do not run this; it prints one JSON line of numbers per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace
from typing import Dict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference  # noqa: E402
from run import load_cell, request_seeds  # noqa: E402


def as_result(ref: Dict, config: Dict, egress: str) -> SimpleNamespace:
    """A reference result in the shape of the program's ``ScenarioResult``
    (the fields ``reference.compare`` reads)."""
    metrics = {
        "jobs_done": float(ref["jobs_done"]),
        "download_pb": ref["download_b"] / 1e15,
        "disk_to_gcs_pb": ref["disk_to_gcs_b"] / 1e15,
        "gcs_to_disk_pb": ref["gcs_to_disk_b"] / 1e15,
        "gcs_used_pb": ref["gcs_used_b"] / 1e15,
        "job_waiting_h_mean": ref["wait_h_mean"],
    }
    names = [s["name"] for s in config["sites"]]
    for i, name in enumerate(names):
        metrics[f"{name}.disk_used_pb"] = ref["disk_used_b"][i] / 1e15
        metrics[f"{name}.tape_to_disk_pb"] = (ref["tape_b"] / 1e15
                                              if i == 0 else 0.0)
    usd = reference.bill(config["pricing"], egress, ref["monthly"])
    monthly = {"gb_seconds": [m[0] for m in ref["monthly"]],
               "egress_bytes": [m[1] for m in ref["monthly"]],
               "class_a": [m[2] for m in ref["monthly"]],
               "class_b": [m[3] for m in ref["monthly"]],
               "full_months": int(config["days"] * 86400
                                  // reference.MONTH_SECONDS)}
    return SimpleNamespace(spec=SimpleNamespace(egress=egress),
                           metrics=metrics, monthly=monthly, **usd)


def control_numbers(cell: Dict, seeds) -> Dict[str, float]:
    """The comparison's numbers with the control in the program's place."""
    cfg, traffic = cell["config"], cell["traffic"]
    pairs = []
    for s in seeds:
        ref = reference.simulate(cfg, traffic, s)
        broken = reference.simulate(cfg, traffic, s, control=True)
        pairs.extend((as_result(broken, cfg, egress), ref)
                     for egress in traffic["egress"])
    return reference.compare(pairs, cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lanes", type=int, default=2,
                    help="lanes per reading, as many as a run compares")
    ap.add_argument("--readings", type=int, default=3)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    for r in range(1, args.readings + 1):
        seeds = request_seeds(args.seed, r, args.lanes)
        print(json.dumps({"workload": args.workload, "seed": args.seed,
                          "reading": r,
                          "numbers": control_numbers(cell, seeds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
