"""Seconds per call in the bulk solve and its write-back
(``Report.meta["wall_breakdown"]`` ``solve_s`` + ``writeback_s``,
``core/lockstep_tiered.py``)."""


def read(w):
    vals = [c["report"].meta["wall_breakdown"]["solve_s"]
            + c["report"].meta["wall_breakdown"]["writeback_s"]
            for c in w.calls if "wall_breakdown" in c["report"].meta]
    return sum(vals) / len(w.calls) if vals else None
