"""The readers of the program's spans and counters, on hand-made reports:
each reads per call over every call of the window, and ``None`` where no
call recorded what it reads."""

from types import SimpleNamespace

import pytest

from benchlib.harness import Window
from benchlib.spec import load_reader

# [name, parent, t0_s, dur_s]; the root first
POD = [["eidola.simulate", None, 0.0, 1.0],
       ["entry.scenario", 0, 0.0, 0.01],
       ["cluster.init", 0, 0.02, 0.03],
       ["program.build", 0, 0.05, 0.2],
       ["runtime.gc", 3, 0.1, 0.05],
       ["engine.select", 0, 0.25, 0.04],
       ["lockstep.compile", 0, 0.3, 0.3],
       ["lockstep.solve", 0, 0.6, 0.3],
       ["lockstep.writeback", 0, 0.9, 0.05],
       ["entry.report", 0, 0.95, 0.04],
       ["runtime.gc", 0, 0.97, 0.02]]  # overlaps entry.report's end
REPLAY = [["eidola.simulate", None, 0.0, 0.01],
          ["entry.scenario", 0, 0.0, 0.003],
          ["entry.traces", 0, 0.003, 0.001],
          ["engine.setup", 0, 0.004, 0.002],
          ["engine.run", 0, 0.0065, 0.002],
          ["entry.report", 0, 0.0085, 0.001]]


def window(*metas):
    calls = [{"report": SimpleNamespace(meta=m, wall_time_s=0.0), "wall_s": 1.0}
             for m in metas]
    return Window(calls, window_s=1.0, setup_s=1.0)


POD_W = window({"spans": POD, "counters": {}}, {"spans": POD, "counters": {}})
REPLAY_W = window({"spans": REPLAY, "counters": {"engine.events": 400}},
                  {"spans": REPLAY, "counters": {"engine.events": 600}})
PARENT_W = window({}, {"closed_loop": True})  # a program that records none


@pytest.mark.parametrize("name, w, want", [
    ("entry.scenario_s", POD_W, 0.01),
    ("entry.scenario_s", REPLAY_W, 0.003),
    ("entry.report_s", POD_W, 0.04),
    ("entry.report_s", REPLAY_W, 0.001),
    ("cluster.setup_s", POD_W, 0.07),
    ("engine.setup_s", REPLAY_W, 0.003),
    ("engine.event_ns", REPLAY_W, 0.002 * 1e9 / 500),
    ("runtime.gc_s", POD_W, 0.07),
    ("runtime.gc_s", REPLAY_W, 0.0),
    # root 1.0 less the union of its children: 0.0-0.01, 0.02-0.29, 0.3-0.99
    ("entry.unspanned_s", POD_W, 0.03),
    # 0.01 less 0.0-0.006 and 0.0065-0.0095
    ("entry.unspanned_s", REPLAY_W, 0.001),
])
def test_reads_per_call(name, w, want):
    assert load_reader(name)(w) == pytest.approx(want)


@pytest.mark.parametrize("name, w", [
    ("cluster.setup_s", REPLAY_W),
    ("engine.setup_s", POD_W),
    ("engine.event_ns", POD_W),
] + [(n, PARENT_W) for n in (
    "entry.scenario_s", "entry.report_s", "cluster.setup_s", "engine.setup_s",
    "engine.event_ns", "runtime.gc_s", "entry.unspanned_s")])
def test_reads_none_where_nothing_was_recorded(name, w):
    assert load_reader(name)(w) is None


def test_calls_without_the_span_count_in_the_mean():
    w = window({"spans": POD, "counters": {}}, {"spans": REPLAY, "counters": {}})
    assert load_reader("cluster.setup_s")(w) == pytest.approx(0.035)
