#!/usr/bin/env python3
"""Readings of the check's control and faults at a cell's own size.

    python3 bench/tools/control.py --workload dgx_h100_4su.ring_ddp \
        --seeds 11,12,13 --seconds 30 \
        [--fault program|control|altered|stale|half|no_exchange]

Each seed drives a whole run of the cell, the timed path replaced as
``bench/benchlib/faults.py`` describes (``program`` leaves it as it is),
and prints the checked numbers as one JSON line.  The benchmark's own runs
never do this.  It needs no chip: the device is stood in for, and with
``JAX_PLATFORMS=cpu`` several processes can run side by side.
"""

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]

from benchlib import faults  # noqa: E402
from benchlib.harness import run_cell  # noqa: E402
from benchlib.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default="control")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    from repro.core import simulate as program

    if args.fault == "control":
        simulate = faults.control(cell.mix["scenario"])
    elif args.fault == "program":
        simulate = program
    else:
        simulate = faults.program_faults(program)[args.fault]
    for seed in args.seeds.split(","):
        t0 = time.perf_counter()
        r = run_cell(cell, int(seed), args.seconds, False, t_start=t0,
                     device={"platform": "stand-in"}, simulate=simulate)
        print(json.dumps({"workload": cell.name, "fault": args.fault,
                          "seed": int(seed), "correct": r["correct"],
                          "attempted": r["attempted"], "checks": r["checks"],
                          "metrics": r["metrics"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
