"""Seconds per call assembling the ``Report``: the per-node aggregation,
``program_stats`` and the report itself on a closed loop, the report alone
on the replay path (span ``entry.report``, ``core/cluster.py``,
``core/simulator.py``)."""

from benchlib import spans


def read(w):
    return spans.seconds(w, ["entry.report"])
