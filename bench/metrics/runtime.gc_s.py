"""Seconds per call in the interpreter's garbage collections, each recorded
as a ``runtime.gc`` span under the span it interrupted; 0 where the calls
record spans but made no collection."""

from benchlib import spans


def read(w):
    if not any(c["report"].meta.get("spans") for c in w.calls):
        return None
    return spans.seconds(w, ["runtime.gc"]) or 0.0
