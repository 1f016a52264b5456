#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload dgx_h100_4su.ring_ddp --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration, a traffic
mix and its metrics.  Set-up names the device (any platform but ``tpu`` is
an error), builds the seeded call stream and makes one warm-up call; the
window then calls ``repro.core.simulate`` back to back for ``--seconds``;
after it, every call of the window is checked against the scenario's plain
reference.  ``--trace 1`` profiles the window and reports the per-layer
metrics instead of the end-to-end ones.  The last line of standard output
is one JSON object; the checked numbers and their limits are the last lines
of standard error.  The checkout's ``src`` goes on ``sys.path`` here, so no
environment variable is needed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

from benchlib.harness import run_cell  # noqa: E402
from benchlib.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
