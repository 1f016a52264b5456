"""What a ``simulate()`` call answers, and how two answers are compared.

A call's answer is every counter its ``Report`` gives: the aggregate
counters (``flag_reads``, ``nonflag_reads``, ``sim_cycles``,
``kernel_span_ns``, the write tracking tables' registered and enacted
writes, every traffic counter), the monitor's statistics, every device's
traffic counters and, on a closed loop, every device's span and the
fabric's message, byte and queueing counters.  Left out are the
simulator's statistics of itself, not of the simulated system:
``wtt_head_polls`` (the engine's head comparisons; the bulk solver reads 0)
and ``Report.meta``'s ``program_stats``, ``wall_breakdown`` and
``lockstep_reason``.

Two numbers come of a comparison:

* ``counts_off``: how many whole-number fields differ, a field present on
  one side only counted as differing.  Counts are exact.
* ``time_gap``: the largest relative gap ``|got - want| / max(|want|, 1)``
  of any time in ns.  Times are float64 sums; the program may add them in
  another order than the reference, which moves the last bits only.
"""

from __future__ import annotations

from typing import Dict, Tuple

Answer = Dict[Tuple, float]


def answer_of(report) -> Answer:
    """Flatten a Report; a reference put in the program's place (the
    check's control) hands its answer over as is."""
    if isinstance(getattr(report, "answer", None), dict):
        return report.answer
    out: Answer = {
        ("flag_reads",): report.flag_reads,
        ("nonflag_reads",): report.nonflag_reads,
        ("kernel_span_ns",): report.kernel_span_ns,
        ("sim_cycles",): report.sim_cycles,
        ("wtt_registered",): report.wtt_registered,
        ("wtt_enacted",): report.wtt_enacted,
    }
    for k, v in report.traffic.items():
        out[("traffic", k)] = v
    for k, v in report.monitor_stats.items():
        out[("monitor", k)] = v
    for dev, counters in report.per_device.items():
        for k, v in counters.items():
            out[("device", int(dev), k)] = v
    for dev, v in report.meta.get("device_spans_ns", {}).items():
        out[("span_ns", int(dev))] = v
    for k, v in report.meta.get("fabric", {}).items():
        out[("fabric", k)] = v
    return out


def compare(got: Answer, want: Answer) -> Tuple[int, float, Tuple]:
    """``(counts_off, time_gap, field of the widest gap)``."""
    off, gap, where = 0, 0.0, ()
    for key in want.keys() | got.keys():
        if key not in got or key not in want:
            off += 1
            where = where or key
            continue
        g, w = got[key], want[key]
        if isinstance(w, int):  # the reference says it is a count
            if g != w:
                off += 1
                where = where or key
            continue
        rel = abs(float(g) - float(w)) / max(abs(float(w)), 1.0)
        if rel > gap:
            gap = rel
            if not off:
                where = key
    return off, gap, where
