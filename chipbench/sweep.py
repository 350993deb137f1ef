#!/usr/bin/env python3
"""Finds an open-loop cell's knee: the highest arrival rate the program
sustains, by a sweep on the chip.  The cell then offers a fixed rate below
it, written in its traffic file; this tool is for a later benchmark PR
that finds the knee again.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r> [<r> ...]

One process and one set of weights; for each rate, a window of
``--seconds`` with the traffic's rate replaced.  Prints a JSON line per
rate: latency p50 and p90, requests served per second over the whole span,
and the drain (how long after the window's end the last due request was
served), which grows with the rate once the queue cannot clear.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import loader  # noqa: E402
from chipbench.run import NoDevice, open_devices  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = loader.load_cell(args.workload, trace=False)
    try:
        open_devices(cell, args.rehearse)
    except NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    driver = cell.driver(cell, seed=args.seed, seconds=args.seconds, rehearse=args.rehearse)
    driver.setup()
    for rate in args.rates:
        driver.traffic["rate_rps"] = rate
        driver.plan()
        rec = driver.run(Tracer(enabled=False, start_s=0, seconds=0))
        lat = np.asarray([r["end"] - r["due"] for r in rec["requests"]])
        end = max(c["end"] for c in rec["calls"])
        print(json.dumps({
            "rate_rps": rate, "requests": len(lat),
            "latency_p50_s": float(np.percentile(lat, 50)),
            "latency_p90_s": float(np.percentile(lat, 90)),
            "served_per_s": len(lat) / end, "drain_s": end - args.seconds,
            "mean_call_s": float(np.mean([c["end"] - c["start"] for c in rec["calls"]])),
            "mean_call_size": float(np.mean([c["n"] for c in rec["calls"]])),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
