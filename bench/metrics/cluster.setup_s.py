"""Seconds per call setting up a closed loop around the program build:
resolving the fabric (span ``cluster.init``) and choosing the engine, the
timeline and lockstep support checks and the plan-cache lookup (span
``engine.select``), ``core/cluster.py``."""

from benchlib import spans


def read(w):
    return spans.seconds(w, ["cluster.init", "engine.select"])
