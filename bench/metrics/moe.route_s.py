"""Seconds per call in span ``moe.route``: the MoE scenario's gate,
expert placement and pair matrix, inside ``entry.scenario``
(``core/scenarios/moe_ep.py``)."""

from benchlib import spans


def read(w):
    return spans.seconds(w, ["moe.route"])
