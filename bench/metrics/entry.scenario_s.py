"""Seconds per call resolving the call's shape, validating its
``SimConfig`` and constructing the scenario (span ``entry.scenario``,
``core/scenario.simulate``)."""

from benchlib import spans


def read(w):
    return spans.seconds(w, ["entry.scenario"])
