"""Find a cell's knee once, on the chip: the highest offered rate at which
the backlog does not grow over the window.

    python3 chipbench/sweep.py --workload bitcoin4k.churn --part churn \
        --rates 6 9 12 15 --seconds 20 --seed 5

Runs the cell once per rate (``--part`` names the traffic part whose
``rate_per_s`` is swept) and prints, per rate, the p50 and p95 latency of
that part's requests and the median latency of the window's first and
last thirds.  A last third that reads well above the first is a backlog
that grows.  A builder's tool: the checks never run it, and a cell's rate
is fixed in its traffic file from what this showed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--part", choices=("churn", "routes"), required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    lane = "event" if args.part == "churn" else "route"
    for rate in args.rates:
        res = run.run_cell(run.ROOT, args.workload, args.seed, args.seconds,
                           False, rates={args.part: rate})
        pts = np.asarray(res["samples"][lane] or [[0.0, np.nan]])
        third = args.seconds / 3
        first = pts[pts[:, 0] < third, 1]
        last = pts[pts[:, 0] >= 2 * third, 1]
        print(json.dumps({
            "rate": rate, "answered": len(pts), "failed": res["failed"],
            "p50_ms": float(np.percentile(pts[:, 1], 50)) * 1e3,
            "p95_ms": float(np.percentile(pts[:, 1], 95)) * 1e3,
            "first_third_p50_ms": float(np.median(first)) * 1e3
            if len(first) else None,
            "last_third_p50_ms": float(np.median(last)) * 1e3
            if len(last) else None,
            "correct": res["correct"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
