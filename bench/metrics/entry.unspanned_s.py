"""Seconds per call inside ``simulate()``'s root span (``eidola.simulate``)
that none of its direct child spans covers: the work no layer names."""

from benchlib import spans


def read(w):
    return spans.unspanned_seconds(w)
