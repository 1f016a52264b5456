"""Spans and counters of one ``simulate()`` call.

``simulate()`` opens one :class:`Recorder` per call; the code underneath
marks its layers with ``with span(name):`` and adds work counts with
:func:`count`.  The call's :class:`~repro.core.simulator.Report` then carries

* ``meta["spans"]``: a list of ``[name, parent, t0_s, dur_s]``, where
  ``parent`` is the index of the enclosing span in the same list (``None``
  for the root) and ``t0_s`` is seconds from the root's start;
* ``meta["counters"]``: ``{name: int}``.

A span is timed whether or not a recorder is open, so the sections a run
reports of itself (``program_stats["construct_wall_s"]``,
``meta["wall_breakdown"]``) are read off their spans and code driven outside
``simulate()`` still gets them.

While the root is open, each garbage collection the calling thread makes is
recorded as a ``runtime.gc`` span under the innermost open span: a layer's
self time (its duration less its children's) then excludes the collector,
and every pause is charged to the layer it interrupted.

When a JAX profiler session is active as the root opens, every span also
opens a ``jax.profiler.TraceAnnotation`` of its name, so the spans land on
the profiler's host plane, on the clock of the device's operations.  This
module never imports JAX: it looks for the profiler in ``sys.modules``.
"""

from __future__ import annotations

import contextvars
import gc
import sys
import threading
from time import perf_counter
from typing import Dict, List, Optional

__all__ = ["GC_SPAN", "Recorder", "count", "span"]

GC_SPAN = "runtime.gc"

_active: contextvars.ContextVar[Optional["Recorder"]] = contextvars.ContextVar(
    "repro_core_spans", default=None
)


def _profiler_annotation():
    """``TraceAnnotation`` while a JAX profiler session records, else None."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return None
    ann = prof.TraceAnnotation
    return ann if ann.is_enabled() else None


class span:
    """``with span(name) as s:`` times the block into ``s.dur`` (seconds)
    and, inside a recorded call, records it under the innermost open span."""

    __slots__ = ("name", "dur", "_rec", "_idx", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.dur = 0.0

    def __enter__(self) -> "span":
        rec = self._rec = _active.get()
        if rec is None:
            self._t0 = perf_counter()
        else:
            rec._enter(self)
        return self

    def __exit__(self, *exc) -> None:
        if self._rec is None:
            self.dur = perf_counter() - self._t0
        else:
            self._rec._exit(self)


def count(name: str, n: int) -> None:
    """Add ``n`` to the recorded call's counter ``name`` (no-op outside one)."""
    rec = _active.get()
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + n


class Recorder:
    """One call's spans and counters: ``with Recorder(root_name) as rec:``
    opens the root span, makes the recorder current and times the calling
    thread's garbage collections until the root closes."""

    def __init__(self, root: str):
        self.spans: List[list] = []
        self.counters: Dict[str, int] = {}
        self._root = span(root)
        self._open: List[int] = []  # indices of the open spans, innermost last
        # collections go to their own list, so that a collection landing
        # between a span's index and its append cannot shift the index
        self._gcs: List[list] = []
        self._gc_at: tuple = (0.0, None, None)
        self._t0 = 0.0
        self._thread = threading.get_ident()
        self._annotate = _profiler_annotation()
        self._token = None

    def __enter__(self) -> "Recorder":
        self._token = _active.set(self)
        self._root.__enter__()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)
        self._root.__exit__(*exc)
        _active.reset(self._token)
        self.spans.extend(self._gcs)

    def _enter(self, s: span) -> None:
        t = perf_counter()
        if not self._open:
            self._t0 = t
        s._t0 = t
        s._idx = idx = len(self.spans)
        self.spans.append([s.name, self._open[-1] if self._open else None,
                           t - self._t0, 0.0])
        self._open.append(idx)
        if self._annotate is not None:
            s._ann = self._annotate(s.name)
            s._ann.__enter__()

    def _exit(self, s: span) -> None:
        if self._annotate is not None:
            s._ann.__exit__(None, None, None)
        s.dur = perf_counter() - s._t0
        self.spans[s._idx][3] = s.dur
        self._open.pop()

    def _on_gc(self, phase: str, info: Dict) -> None:
        if threading.get_ident() != self._thread:
            return
        if phase == "start":
            ann = None
            if self._annotate is not None:
                ann = self._annotate(GC_SPAN)
                ann.__enter__()
            self._gc_at = (perf_counter(), self._open[-1], ann)
        else:
            t0, parent, ann = self._gc_at
            if ann is not None:
                ann.__exit__(None, None, None)
            self._gcs.append([GC_SPAN, parent, t0 - self._t0,
                              perf_counter() - t0])
