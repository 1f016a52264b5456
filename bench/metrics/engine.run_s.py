"""Seconds per call in the event engine's loop (``Report.wall_time_s`` on
the single-detailed-device path): ``core/engine.py`` with the interpreter of
``core/target.py`` and the write tracking table of ``core/wtt.py``."""


def read(w):
    vals = [c["report"].wall_time_s for c in w.calls
            if not c["report"].meta.get("closed_loop")]
    return sum(vals) / len(w.calls) if vals else None
