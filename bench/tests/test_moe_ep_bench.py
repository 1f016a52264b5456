"""The DeepSeek-V3 EP decode cell's pieces: its check refuses the
lower-precision control and each fault of the timed path on a 16-GPU cut
of the pool; its three readers read the ordered pricing and the router off
hand-made reports, and nothing where no call recorded them; and the
ordered pricing's chain keeps the event engine's float adds when messages
differ in size."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchlib import faults, harness
from benchlib.harness import Window
from benchlib.spec import load_cell, load_reader

CELL = "deepseek_v3_ep144.decode_zipf"


def small():
    cell = load_cell(CELL)
    cell.config = {**cell.config,
                   "deployment": {**cell.config["deployment"], "devices": 16},
                   "sim_config": {**cell.config["sim_config"], "n_egpus": 15}}
    return cell


def run(cell, simulate, seconds=0.3):
    return harness.run_cell(cell, 2**31 + 11, seconds, False,
                            t_start=time.perf_counter(),
                            device={"platform": "stand-in"},
                            simulate=simulate)


def test_program_reads_correct():
    from repro.core import simulate

    r = run(small(), simulate)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 1
    assert set(r["metrics"]) >= {"sim_wall_s", "setup_s"}


def test_control_reads_not_correct():
    cell = small()
    r = run(cell, faults.control(cell.mix["scenario"]))
    assert not r["correct"]
    assert r["checks"]["time_gap"]["value"] > r["checks"]["time_gap"]["limit"]


@pytest.mark.parametrize("fault", ["altered", "stale", "half", "no_exchange"])
def test_fault_reads_not_correct(fault):
    from repro.core import simulate

    r = run(small(), faults.program_faults(simulate)[fault], seconds=0.5)
    assert r["attempted"] > 1
    assert not r["correct"], fault


# [name, parent, t0_s, dur_s]; the root first
EP = [["eidola.simulate", None, 0.0, 0.5],
      ["entry.scenario", 0, 0.0, 0.3],
      ["moe.route", 1, 0.0, 0.25],
      ["lockstep.solve", 0, 0.35, 0.1],
      ["lockstep.ordered", 3, 0.35, 0.04],
      ["lockstep.ordered", 3, 0.4, 0.05]]
RING = [["eidola.simulate", None, 0.0, 0.5],
        ["entry.scenario", 0, 0.0, 0.001],
        ["lockstep.solve", 0, 0.2, 0.1]]


def window(*metas):
    calls = [{"report": SimpleNamespace(meta=m, wall_time_s=0.0), "wall_s": 1.0}
             for m in metas]
    return Window(calls, window_s=1.0, setup_s=1.0)


EP_W = window({"spans": EP, "counters": {"lockstep.ordered_msgs": 41184}},
              {"spans": EP, "counters": {"lockstep.ordered_msgs": 41184}})


@pytest.mark.parametrize("name, want", [
    ("moe.route_s", 0.25),
    ("lockstep.ordered_s", 0.09),
    ("lockstep.ordered_msg_ns", 0.09 * 1e9 / 41184),
])
def test_reads_per_call(name, want):
    assert load_reader(name)(EP_W) == pytest.approx(want)


@pytest.mark.parametrize("name", ["moe.route_s", "lockstep.ordered_s",
                                  "lockstep.ordered_msg_ns"])
@pytest.mark.parametrize("w", [
    window({"spans": RING, "counters": {"lockstep.steps": 10}}),
    window({}, {"closed_loop": True}),  # a program that records none
], ids=["no_such_span", "parent"])
def test_reads_none_where_nothing_was_recorded(name, w):
    assert load_reader(name)(w) is None


def test_ordered_msg_ns_needs_its_counter():
    w = window({"spans": EP, "counters": {}})
    assert load_reader("lockstep.ordered_msg_ns")(w) is None


def _sequential(b0, rdy, ser):
    """The event engine's port: ``start = max(ready, busy); busy = start +
    ser``, one touch at a time."""
    starts, b = [], float(b0)
    for r, s in zip(rdy, ser):
        st = r if r > b else b
        starts.append(st)
        b = st + s
    return np.array(starts), b


@pytest.mark.parametrize("seed", range(6))
def test_chain_with_a_time_per_touch_keeps_the_sequential_adds(seed):
    from repro.core.lockstep_tiered import _chain

    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 400))
    nbytes = rng.integers(8, 20000, size=m) * rng.integers(0, 2, size=m) + 8
    ser = nbytes / 50.0
    # bursts and gaps: runs that queue and runs that restart
    gaps = rng.exponential(ser.mean() * rng.choice([0.2, 1.0, 5.0]), size=m)
    rdy = np.cumsum(gaps) + rng.random()
    b0 = float(rng.random() * ser.sum() * 0.1)
    starts, b = _chain(b0, rdy, ser)
    want, want_b = _sequential(b0, rdy, ser)
    assert np.array_equal(starts, want)
    assert b == want_b


def test_chain_with_one_time_is_unchanged():
    from repro.core.lockstep_tiered import _chain

    rdy = np.array([0.0, 1.0, 1.5, 9.0, 9.1, 30.0])
    starts, b = _chain(0.5, rdy, 2.5)
    want, want_b = _sequential(0.5, rdy, [2.5] * rdy.size)
    assert np.array_equal(starts, want) and b == want_b
