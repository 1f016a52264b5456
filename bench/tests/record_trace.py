#!/usr/bin/env python3
"""Record the small chip trace that ``test_xtrace.py`` reduces.

    python3 bench/tests/record_trace.py   # on a machine with a TPU

A traced window as the harness makes it: the device probe, then three
``simulate()`` calls of the ``eidola_table1.fig6_sweep`` cell.  Writes
``bench/tests/data/probe_window.xplane.pb`` and prints its reduction.
"""

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
from jax.profiler import ProfileOptions, TraceAnnotation  # noqa: E402

from benchlib import harness, traffic, xtrace  # noqa: E402
from benchlib.spec import load_cell  # noqa: E402


def main() -> int:
    harness.require_chip(1)
    from repro.core import simulate

    cell = load_cell("eidola_table1.fig6_sweep")
    call = next(traffic.CallStream(cell.config, cell.mix, 1))
    harness.call_simulate(simulate, call)
    probe = harness.make_probe()
    out = HERE / "data" / "probe_window.xplane.pb"
    tmp = harness.TRACE_DIR
    shutil.rmtree(tmp, ignore_errors=True)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("bench.probe"):
            probe()
        for _ in range(3):
            with TraceAnnotation("simulate"):
                harness.call_simulate(simulate, call)
    jax.profiler.stop_trace()
    src = xtrace.newest_xplane(str(tmp))
    out.parent.mkdir(exist_ok=True)
    shutil.copyfile(src, out)
    planes = xtrace.load(str(out))
    for name, lines in planes:
        print(name, [(ln, len(evs), evs[:3]) for ln, evs in lines])
    print(json.dumps(xtrace.reduce_planes(planes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
