#!/usr/bin/env python3
"""The readings that a cell's check limits are set from, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed: the cell's weights and traffic from that seed, a window of
``--seconds`` at the cell's own load, and then the numbers that decide
``correct`` (``reference.compare``) for the program, and, for the first
four seeds (three or more are needed; each costs a second
reference pass on the chip), the control's at the same positions:
the reference computed with float8 (e4m3) operands, the step below the
bfloat16 that the configurations state.  Prints one JSON line per seed,
then the largest program reading and the smallest control reading of each
number.  The benchmark's own runs never run the
control.  ``--rehearse`` runs it on the CPU at rehearsal sizes.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import loader  # noqa: E402
from chipbench import program as PG  # noqa: E402
from chipbench import reference as R  # noqa: E402
from chipbench.run import NoDevice, open_devices  # noqa: E402
from chipbench.trace import Tracer  # noqa: E402

CONTROL_SEEDS = 4


def readings(cell, seed: int, seconds: float, rehearse: bool, control: bool) -> dict:
    """The program's and (with ``control``) the control's numbers for one seed."""
    fam = cell.family
    model = fam.sizes(cell.config, rehearse)
    driver = cell.driver(cell, seed=seed, seconds=seconds, rehearse=rehearse)
    driver.setup()
    record = driver.run(Tracer(enabled=False, start_s=0, seconds=0))
    samples = driver.release(record)
    tokens, rows = PG.reference_inputs(samples, driver.max_out)
    ref = fam.reference_logits(model, seed, tokens, rows)
    out = {"seed": seed, "tokens_compared": int(sum(len(s.served) for s in samples)),
           "program": R.compare([s.served for s in samples], ref)}
    if control:
        ctl = fam.reference_logits(model, seed, tokens, rows, quantize="fp8")
        out["control"] = R.control_readings(ref, ctl, [len(s.served) for s in samples])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = loader.load_cell(args.workload, trace=False)
    try:
        open_devices(cell, args.rehearse)
    except NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    rows = []
    for i, seed in enumerate(args.seeds):
        rows.append(readings(cell, seed, args.seconds, args.rehearse, i < CONTROL_SEEDS))
        print(json.dumps(rows[-1]), flush=True)
    names = rows[0]["program"].keys()
    ctl = [r["control"] for r in rows if "control" in r]
    print(json.dumps({"lower": {k: max(r["program"][k] for r in rows) for k in names},
                      "control_least": {k: min(c[k] for c in ctl) for k in names},
                      "seeds": len(rows), "control_seeds": len(ctl)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
