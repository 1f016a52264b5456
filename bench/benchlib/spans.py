"""Read the program's own spans and counters out of a run's reports.

A ``simulate()`` call that records them leaves ``Report.meta["spans"]``, a
list of ``[name, parent, t0_s, dur_s]`` (``parent`` the index of the
enclosing span, ``None`` for the root, which comes first), and
``Report.meta["counters"]``.  Each reading here is per call, averaged over
every call of the window, and ``None`` where no call recorded what it reads:
a program that records no spans, or a cell whose calls never open that span.
"""

from __future__ import annotations

from typing import Iterable, List, Optional


def _spans(call) -> List[list]:
    return call["report"].meta.get("spans") or []


def seconds(w, names: Iterable[str]) -> Optional[float]:
    """Seconds per call inside the spans named ``names``."""
    names = set(names)
    total, found = 0.0, False
    for c in w.calls:
        for name, _, _, dur in _spans(c):
            if name in names:
                total += dur
                found = True
    return total / len(w.calls) if found else None


def counter(w, name: str) -> Optional[float]:
    """Counter ``name`` per call."""
    vals = [c["report"].meta.get("counters", {}).get(name) for c in w.calls]
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(w.calls) if vals else None


def unspanned(spans: List[list]) -> float:
    """The root's duration less the union of its direct children's spans."""
    _, _, r0, rdur = spans[0]
    covered, end = 0.0, r0
    for s, e in sorted((t0, t0 + dur) for _, parent, t0, dur in spans
                       if parent == 0):
        s, e = max(s, end), min(e, r0 + rdur)
        if e > s:
            covered += e - s
            end = e
    return rdur - covered


def unspanned_seconds(w) -> Optional[float]:
    """Seconds per call of the root span that no direct child covers."""
    vals = [unspanned(_spans(c)) for c in w.calls if _spans(c)]
    return sum(vals) / len(w.calls) if vals else None
