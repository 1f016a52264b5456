"""Per-call spans and counters (``repro.core.spans``): nesting, the
collector's pauses, the profiler hook, and the spans ``simulate()`` leaves
in ``Report.meta`` on a lockstep closed loop and on the replay path."""

import gc
import sys
import types

import pytest

from repro.core import SimConfig, simulate
from repro.core import scenario as scenario_mod
from repro.core.spans import GC_SPAN, Recorder, count, span

RING = dict(devices=4, closed_loop=True, lockstep=True, collect_segments=False)
RING_SPANS = {"eidola.simulate", "entry.scenario", "cluster.init",
              "program.build", "engine.select", "lockstep.compile",
              "lockstep.solve", "lockstep.writeback", "entry.report"}
REPLAY_SPANS = {"eidola.simulate", "entry.scenario", "entry.traces",
                "engine.setup", "engine.run", "entry.report"}
CALLS = {
    "ring_allreduce": (RING, RING_SPANS),
    "gemv_allreduce": (dict(collect_segments=False), REPLAY_SPANS),
}


def _self_time(spans, i):
    return spans[i][3] - sum(s[3] for s in spans if s[1] == i)


def test_nesting_parents_and_self_time():
    with Recorder("root") as rec:
        with span("a"):
            with span("a.1"):
                pass
            with span("a.2") as inner:
                count("things", 2)
        with span("b"):
            count("things", 3)
    count("things", 100)  # outside the call: dropped
    spans = [s for s in rec.spans if s[0] != GC_SPAN]
    assert [(s[0], s[1]) for s in spans] == [
        ("root", None), ("a", 0), ("a.1", 1), ("a.2", 1), ("b", 0)]
    assert spans[0][2] == 0.0
    assert spans[3][3] == inner.dur
    for name, parent, t0, dur in spans[1:]:
        p = rec.spans[parent]
        assert p[2] <= t0 and t0 + dur <= p[2] + p[3] + 1e-9, name
    assert spans[2][2] + spans[2][3] <= spans[3][2] + 1e-9  # siblings in order
    assert 0.0 <= _self_time(rec.spans, 1) <= rec.spans[1][3]
    assert rec.counters == {"things": 5}


def test_span_times_without_a_recorder():
    with span("alone") as s:
        count("ignored", 1)
    assert s.dur > 0.0


def test_forced_collection_is_a_child_of_the_open_span():
    with Recorder("root") as rec:
        with span("layer"):
            gc.collect()
    gc.collect()  # after the root: not recorded
    layer = [s[0] for s in rec.spans].index("layer")
    pauses = [s for s in rec.spans if s[0] == GC_SPAN and s[1] == layer]
    assert pauses
    for _, _, t0, dur in pauses:
        assert rec.spans[layer][2] <= t0
        assert t0 + dur <= rec.spans[layer][2] + rec.spans[layer][3] + 1e-9
    assert _self_time(rec.spans, layer) < rec.spans[layer][3]
    assert gc.callbacks.count(rec._on_gc) == 0


class _FakeAnnotation:
    enabled = True
    opened = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.opened.append(self.name)

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("enabled", [True, False])
def test_profiler_annotations_follow_the_session(monkeypatch, enabled):
    fake = types.ModuleType("jax.profiler")
    fake.TraceAnnotation = _FakeAnnotation
    monkeypatch.setitem(sys.modules, "jax.profiler", fake)
    monkeypatch.setattr(_FakeAnnotation, "enabled", enabled)
    monkeypatch.setattr(_FakeAnnotation, "opened", [])
    with Recorder("root"), span("layer"):
        gc.collect()
    if enabled:
        assert _FakeAnnotation.opened[:2] == ["root", "layer"]
        assert GC_SPAN in _FakeAnnotation.opened
    else:
        assert _FakeAnnotation.opened == []


def test_no_annotation_is_attempted_without_jax(monkeypatch):
    for mod in [m for m in sys.modules if m == "jax" or m.startswith("jax.")]:
        monkeypatch.delitem(sys.modules, mod)
    with Recorder("root") as rec, span("layer"):
        gc.collect()
    assert rec._annotate is None
    assert "jax" not in sys.modules  # nor was JAX imported to find out


def _answer(r):
    out = dict(
        traffic=r.traffic, per_device=r.per_device, flag_reads=r.flag_reads,
        nonflag_reads=r.nonflag_reads, kernel_span_ns=r.kernel_span_ns,
        sim_cycles=r.sim_cycles, wtt_registered=r.wtt_registered,
        wtt_enacted=r.wtt_enacted, wtt_head_polls=r.wtt_head_polls,
        monitor_stats=r.monitor_stats,
    )
    for k in ("device_spans_ns", "fabric", "lockstep_reason"):
        out[k] = r.meta.get(k)
    return out


class _NoRecorder:
    def __init__(self, root):
        self.spans, self.counters = [], {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


@pytest.mark.parametrize("name", sorted(CALLS))
def test_simulate_records_every_layer(name, monkeypatch):
    kw, want = CALLS[name]
    cfg = SimConfig(workgroups=16)
    r = simulate(name, cfg, **kw)
    spans = r.meta["spans"]
    names = {s[0] for s in spans}
    assert want <= names
    assert names - want <= {GC_SPAN}
    assert spans[0][:3] == ["eidola.simulate", None, 0.0]
    assert all(s[1] is not None for s in spans[1:])
    root_end = spans[0][3]
    for s_name, parent, t0, dur in spans[1:]:
        assert parent == 0 or s_name == GC_SPAN, s_name  # layers sit under the root
        p = spans[parent]
        assert p[2] <= t0 and t0 + dur <= p[2] + p[3] + 1e-9, s_name
        assert t0 + dur <= root_end + 1e-9
    dur = {s[0]: s[3] for s in spans if s[0] != GC_SPAN}
    if r.closed_loop:
        assert r.meta["program_stats"]["construct_wall_s"] == dur["program.build"]
        assert r.meta["wall_breakdown"] == {
            "compile_s": dur["lockstep.compile"],
            "solve_s": dur["lockstep.solve"],
            "writeback_s": dur["lockstep.writeback"],
        }
        assert "engine.events" not in r.meta["counters"]
    else:
        assert r.wall_time_s == dur["engine.run"]
        assert r.meta["counters"]["engine.events"] > 0
    monkeypatch.setattr(scenario_mod, "Recorder", _NoRecorder)
    bare = simulate(name, cfg, **kw)
    assert bare.meta["spans"] == [] and bare.meta["counters"] == {}
    assert _answer(r) == _answer(bare)


def test_event_count_repeats_exactly():
    cfg = SimConfig(workgroups=16)
    a = simulate("gemv_allreduce", cfg, collect_segments=False)
    b = simulate("gemv_allreduce", cfg, collect_segments=False)
    assert a.meta["counters"] == b.meta["counters"]
    cyc = simulate("gemv_allreduce", cfg.with_(engine="cycle"),
                   collect_segments=False)
    assert "engine.events" not in cyc.meta["counters"]
    assert "engine.run" in {s[0] for s in cyc.meta["spans"]}


def test_vector_engine_wall_is_its_span():
    r = simulate("gemv_allreduce", SimConfig(workgroups=16).with_(engine="vector"),
                 collect_segments=False)
    runs = [s for s in r.meta["spans"] if s[0] == "engine.run"]
    assert len(runs) == 1 and runs[0][1] == 0
    assert r.wall_time_s == runs[0][3]
