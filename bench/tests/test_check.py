"""The check refuses the lower-precision control and each fault of the
timed path: an altered counter, the state returned unchanged, half of the
workgroups dropped, and the peers' data writes left out.  Each drives a
whole run of the harness with the chip stood in for; the pod cells run at
16 GPUs so that a test run holds them."""

import time

import pytest

from benchlib import faults, harness
from benchlib.spec import load_cell

CELLS = ("dgx_h100_4su.ring_ddp", "eidola_table1.fig6_sweep",
         "dgx_h100_4su.hier_ddp")


def small(name):
    cell = load_cell(name)
    if "devices" in cell.config["deployment"]:
        cell.config = {**cell.config,
                       "deployment": {**cell.config["deployment"],
                                      "devices": 16},
                       "sim_config": {**cell.config["sim_config"],
                                      "n_egpus": 15}}
    return cell


def run(cell, simulate, seconds=0.3):
    return harness.run_cell(cell, 2**31 + 7, seconds, False,
                            t_start=time.perf_counter(),
                            device={"platform": "stand-in"},
                            simulate=simulate)


@pytest.mark.parametrize("name", CELLS)
def test_program_reads_correct(name):
    from repro.core import simulate

    r = run(small(name), simulate)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 1
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) >= {"sim_wall_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_not_correct(name):
    cell = small(name)
    r = run(cell, faults.control(cell.mix["scenario"]))
    assert not r["correct"]
    assert r["checks"]["time_gap"]["value"] > r["checks"]["time_gap"]["limit"]


@pytest.mark.parametrize("fault", ["altered", "stale", "half", "no_exchange"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_reads_not_correct(name, fault):
    from repro.core import simulate

    r = run(small(name), faults.program_faults(simulate)[fault], seconds=0.5)
    assert r["attempted"] > 1
    assert not r["correct"], fault
