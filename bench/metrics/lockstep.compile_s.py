"""Seconds per call compiling the bulk solver's plan
(``Report.meta["wall_breakdown"]["compile_s"]``, ``core/lockstep_tiered.py``)."""


def read(w):
    vals = [c["report"].meta["wall_breakdown"]["compile_s"]
            for c in w.calls if "wall_breakdown" in c["report"].meta]
    return sum(vals) / len(w.calls) if vals else None
