"""The plain references equal the program on small pods and on the
paper's Table 1 calls, against both the event engine (the simulator's
reference engine) and the default path (the bulk lockstep solver)."""

import pytest

from benchlib import answers, harness
from benchlib.harness import TIME_GAP_LIMIT
from benchlib.spec import load_cell, load_reference

POD = ("dgx_h100_4su.ring_ddp", "dgx_h100_4su.hier_ddp")


def small_pod_call(cell_name, payload, devices=16):
    """A call of the pod cell's deployment cut to ``devices`` GPUs."""
    cell = load_cell(cell_name)
    call = harness.traffic.CallStream(cell.config, cell.mix, 0).__next__()
    call["sim_config"] = {**call["sim_config"], "n_egpus": devices - 1}
    call["params"] = {**call["params"], "devices": devices,
                      "payload_bytes": payload}
    return call


def gap(call, **engine):
    from repro.core import simulate

    def sim(scenario, cfg, **kw):
        return simulate(scenario, cfg, **kw, **engine)

    got = answers.answer_of(harness.call_simulate(sim, call))
    want = load_reference(call["scenario"]).answer(call)
    return answers.compare(got, want)


@pytest.mark.parametrize("cell", POD)
@pytest.mark.parametrize("payload", [1 << 20, 25 << 20, 327680000])
@pytest.mark.parametrize("engine", [{}, {"lockstep": False, "timeline": False}],
                         ids=["default", "event"])
def test_pod_reference_equals_program(cell, payload, engine):
    off, time_gap, where = gap(small_pod_call(cell, payload), **engine)
    assert off == 0, where
    assert time_gap <= TIME_GAP_LIMIT, where
    if engine:  # the event engine adds times in the reference's order
        assert time_gap == 0.0, where


@pytest.mark.parametrize("cell", POD)
def test_pod_reference_with_several_dispatch_waves(cell):
    call = small_pod_call(cell, 25 << 20, devices=24)
    call["sim_config"] = {**call["sim_config"], "workgroups": 300}
    assert gap(call, lockstep=False, timeline=False) == (0, 0.0, ())


@pytest.mark.parametrize("delay_ns", [0.0, 40000.0, 17321.5])
def test_table1_reference_equals_program(delay_ns):
    cell = load_cell("eidola_table1.fig6_sweep")
    call = harness.traffic.CallStream(cell.config, cell.mix, 0).__next__()
    call["params"] = {"flag_delays_ns": delay_ns}
    assert gap(call) == (0, 0.0, ())
