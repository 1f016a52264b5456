"""Microseconds of the tiered bulk solve per plan instruction: seconds in
span ``lockstep.solve`` times 1e6 over counter ``lockstep.steps`` (the
``"p"``, ``"w"`` and ``"aw"`` instructions run), both per call
(``core/lockstep_tiered.py``)."""

from benchlib import spans


def read(w):
    solve_s = spans.seconds(w, ["lockstep.solve"])
    steps = spans.counter(w, "lockstep.steps")
    if solve_s is None or not steps:
        return None
    return solve_s * 1e6 / steps
