"""Nanoseconds of ordered pricing per message: seconds in span
``lockstep.ordered`` times 1e9 over counter ``lockstep.ordered_msgs`` (the
messages it priced), both per call (``core/lockstep_tiered.py``)."""

from benchlib import spans


def read(w):
    ordered_s = spans.seconds(w, ["lockstep.ordered"])
    msgs = spans.counter(w, "lockstep.ordered_msgs")
    if ordered_s is None or not msgs:
        return None
    return ordered_s * 1e9 / msgs
