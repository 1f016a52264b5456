"""Seconds per call building the per-rank programs and the cluster's nodes
(``Report.meta["program_stats"]["construct_wall_s"]``): ``core/scenario.py``
SymbolicProgram construction and ``core/cluster.py``."""


def read(w):
    vals = [c["report"].meta["program_stats"]["construct_wall_s"]
            for c in w.calls if "program_stats" in c["report"].meta]
    return sum(vals) / len(w.calls) if vals else None
