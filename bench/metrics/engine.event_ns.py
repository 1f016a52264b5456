"""Nanoseconds of the event engine's loop per calendar entry it acted on:
seconds in span ``engine.run`` times 1e9 over counter ``engine.events``
(write groups enacted and device transition batches fired), both per call
(``core/engine.py``)."""

from benchlib import spans


def read(w):
    run_s = spans.seconds(w, ["engine.run"])
    events = spans.counter(w, "engine.events")
    if run_s is None or not events:
        return None
    return run_s * 1e9 / events
