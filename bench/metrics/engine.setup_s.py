"""Seconds per call setting up the single-detailed-device replay: the
peers' traces (span ``entry.traces``, the scenario's ``traces()``) and the
engine's state, directory memory, target device and the write tracking
table's registrations (span ``engine.setup``, ``core/simulator.py``)."""

from benchlib import spans


def read(w):
    return spans.seconds(w, ["entry.traces", "engine.setup"])
