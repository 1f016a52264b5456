"""Seconds per call in span ``lockstep.ordered``: the tiered solve's
pricing of link ports shared by several messages, in the event engine's
global message order, inside ``lockstep.solve``
(``core/lockstep_tiered.py``)."""

from benchlib import spans


def read(w):
    return spans.seconds(w, ["lockstep.ordered"])
