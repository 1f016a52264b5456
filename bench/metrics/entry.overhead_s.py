"""Seconds per call outside the program's own timed sections: the host
clock around each call, less program build, plan compile, solve and
write-back on a closed loop (``Report.meta``), or less the engine's wall
(``Report.wall_time_s``) on the single-detailed-device path.  What is left
is ``repro.core.scenario.simulate``: scenario and workload construction,
trace build and ``Report`` assembly."""


def sections(report):
    meta = report.meta
    wb = meta.get("wall_breakdown")
    if meta.get("closed_loop") and wb is not None:
        return (meta["program_stats"]["construct_wall_s"] + wb["compile_s"]
                + wb["solve_s"] + wb["writeback_s"])
    return report.wall_time_s


def read(w):
    if not w.calls:
        return None
    return sum(c["wall_s"] - sections(c["report"]) for c in w.calls) / len(w.calls)
