"""The reader of the tiered solve's time per instruction, on hand-made
reports: seconds in span ``lockstep.solve`` times 1e6 over counter
``lockstep.steps``, summed over the window's calls, and ``None`` where no
call recorded the counter."""

from types import SimpleNamespace

import pytest

from benchlib.harness import Window
from benchlib.spec import load_reader

# [name, parent, t0_s, dur_s]; the root first
POD = [["eidola.simulate", None, 0.0, 1.0],
       ["lockstep.compile", 0, 0.3, 0.3],
       ["lockstep.solve", 0, 0.6, 0.3],
       ["lockstep.writeback", 0, 0.9, 0.05]]
REPLAY = [["eidola.simulate", None, 0.0, 0.01],
          ["engine.run", 0, 0.0065, 0.002]]


def window(*metas):
    calls = [{"report": SimpleNamespace(meta=m, wall_time_s=0.0), "wall_s": 1.0}
             for m in metas]
    return Window(calls, window_s=1.0, setup_s=1.0)


def test_reads_solve_us_per_instruction():
    w = window({"spans": POD, "counters": {"lockstep.steps": 4000}},
               {"spans": POD, "counters": {"lockstep.steps": 2000}})
    assert load_reader("lockstep.step_us")(w) == pytest.approx(0.3 * 1e6 / 3000)


@pytest.mark.parametrize("w", [
    window({"spans": POD, "counters": {}}, {"spans": POD, "counters": {}}),
    window({"spans": REPLAY, "counters": {"engine.events": 400}}),
    window({}, {"closed_loop": True}),  # a program that records none
], ids=["solve_without_counter", "replay", "parent"])
def test_reads_none_where_nothing_was_recorded(w):
    assert load_reader("lockstep.step_us")(w) is None
