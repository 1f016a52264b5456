"""``bench/run.py`` runs on a TPU only: on the CPU it exits non-zero and
prints no result, with no ``PYTHONPATH`` set."""

import os
import subprocess
import sys

from benchlib.spec import ROOT


def test_run_refuses_a_cpu():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "eidola_table1.fig6_sweep", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "platform 'cpu'" in p.stderr
