"""Trace reduction: busy time, idle share and the breakdown.

The recorded trace is a traced window of the harness on a TPU v5e: the
probe, then three ``simulate()`` calls of the paper's Table 1 kernel
(recorded under SyncMon; ``record_trace.py`` makes a new one from the
Table 1 cell).  Reading it loads no TPU library.
"""

from pathlib import Path

import pytest

from benchlib import xtrace

DATA = Path(__file__).resolve().parent / "data" / "probe_window.xplane.pb"


def window(ops, spans, lo=0.0, hi=1e9):
    host = [("bench.window", lo, hi - lo)] + spans
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", ops)])]


def test_no_device_op_reads_idle_exactly_one():
    r = xtrace.reduce_planes(window([], [("simulate", 1e8, 5e8)]))
    assert r["busy_s"] == 0.0
    assert r["idle_share"] == 1.0
    assert r["device_ops"] == []
    assert r["idle_gaps"][0] == ["simulate", 0.5]


def test_busy_is_the_union_of_ops_inside_the_window():
    ops = [("a", 1e8, 2e8), ("b", 2e8, 2e8), ("a", 9e8, 3e8)]  # overlap, clipped
    r = xtrace.reduce_planes(window(ops, []))
    assert r["busy_s"] == pytest.approx(0.4)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["device_ops"][0] == ["a", pytest.approx(0.3)]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(0.6)


def test_gaps_are_cut_by_host_span():
    ops = [("probe", 0.0, 1e7)]
    spans = [("bench.probe", 0.0, 1e7), ("simulate", 2e7, 3e8),
             ("simulate", 4e8, 1e8)]
    r = xtrace.reduce_planes(window(ops, spans))
    names = dict((n, 0.0) for n, _ in r["idle_gaps"])
    for n, v in r["idle_gaps"]:
        names[n] += v
    assert names["simulate"] == pytest.approx(0.4)
    assert names["host.other"] == pytest.approx(0.59)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        xtrace.reduce_planes([("/host:CPU", [("python", [])])])


def test_recorded_chip_trace():
    planes = xtrace.load(str(DATA))
    r = xtrace.reduce_planes(planes)
    assert r["chips"] == 1
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    assert r["device_ops"]
    assert "simulate" in [n for n, _ in r["idle_gaps"]]
    maps = Path("/proc/self/maps")
    if maps.exists():
        assert "libtpu" not in maps.read_text()
