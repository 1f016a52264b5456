"""One run of one cell: set-up, a timed window of ``simulate()`` calls, the
check of every answer against the plain reference, and the result line.

Set-up names the chip, loads the cell's files, builds its call stream and
makes one warm-up call of the cell's own shape.  The window is a closed
loop of one client, a user's sweep: ``repro.core.simulate`` called back to
back with public arguments only, each call with its parameters from the
stream.  It closes at the end of the first call that finishes past
``seconds``; that call counts.  The interpreter's garbage collector is left
as a user's process has it; the window's collections are counted and timed
(``gc.callbacks``) and printed on standard error.

The simulator runs nothing on the chip.  So that a traced run shows the
device at all, one tiny jitted operation (``bench.probe``) runs at the
window's start; its compilation is part of set-up.

After the window, every call it completed is worked out again by the
scenario's plain reference (``bench/refs/<scenario>.py``), once per
distinct call.
"""

from __future__ import annotations

import contextlib
import gc
import json
import shutil
import sys
import time
import traceback
from collections import Counter
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from . import answers, traffic, xtrace
from .spec import ROOT, Cell, load_reference

#: whole-number fields of the answers that may differ from the reference's
COUNTS_OFF_LIMIT = 0
#: largest relative gap of a time in ns (see PERF.md for the readings)
TIME_GAP_LIMIT = 1e-10
#: calls of the window that may raise
FAILED_CALLS_LIMIT = 0

TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


@dataclass
class Window:
    """What a metric reader sees of one run: one record per completed call
    (``wall_s`` and the ``Report``), the window's and set-up's seconds, and
    the trace's reduction when the run was traced."""

    calls: List[Dict]
    window_s: float
    setup_s: float
    trace: Optional[Dict] = None


def require_chip(chips: int) -> Dict:
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"error: the benchmark runs on a TPU, but JAX found platform "
            f"{platform!r} ({len(devs)} device(s)); it never runs elsewhere")
    if len(devs) < chips:
        raise SystemExit(
            f"error: the cell needs {chips} TPU chips, found {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def use_checkout_cache() -> None:
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def make_probe() -> Callable[[], None]:
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: jnp.cumsum(x * 2 + 1))
    x = jnp.arange(1024, dtype=jnp.int32)

    def probe() -> None:
        fn(x).block_until_ready()

    probe()  # compiles, or loads from the cache, in set-up
    return probe


def call_simulate(simulate, call: Dict):
    """``simulate()`` with the call's public arguments."""
    from repro.core import HardwareSpec, SimConfig, SyncPolicy

    fields = dict(call["sim_config"])
    fields["sync"] = SyncPolicy(fields["sync"])
    kw = dict(call["params"])
    if call.get("hardware"):
        kw["hw"] = HardwareSpec(**call["hardware"])
    return simulate(call["scenario"], SimConfig(**fields),
                    collect_segments=False, **kw)


class GcClock:
    """Collections the interpreter makes while this is in ``gc.callbacks``,
    and their seconds, by generation."""

    def __init__(self) -> None:
        self.count = Counter()
        self.seconds = Counter()
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            self.count[g] += 1
            self.seconds[g] += time.perf_counter() - self._t0


def run_window(simulate, stream, seconds: float, annotate) -> tuple:
    calls: List[Dict] = []
    failed = 0
    start = time.perf_counter()
    while True:
        call = next(stream)
        t0 = time.perf_counter()
        try:
            with annotate("simulate"):
                report = call_simulate(simulate, call)
        except Exception:  # a failed call counts against correct
            failed += 1
            traceback.print_exc(limit=4, file=sys.stderr)
            report = None
        t1 = time.perf_counter()
        if report is not None:
            calls.append({"call": call, "report": report, "wall_s": t1 - t0})
        if t1 - start >= seconds:
            return calls, failed, t1 - start


def check_answers(calls: List[Dict]) -> Dict:
    """Work every call of the window out again on the plain reference,
    once per distinct call."""
    want: Dict[str, Dict] = {}
    refs: Dict[str, object] = {}
    off, gap, where, gap_at = 0, 0.0, (), ()
    for rec in calls:
        call = rec["call"]
        key = json.dumps(call, sort_keys=True)
        if key not in want:
            if call["scenario"] not in refs:
                refs[call["scenario"]] = load_reference(call["scenario"])
            want[key] = refs[call["scenario"]].answer(call)
        o, g, w = answers.compare(answers.answer_of(rec["report"]), want[key])
        if o and not off:
            where = w
        off += o
        if g > gap:
            gap, gap_at = g, w
    where = where or gap_at
    return {"counts_off": off, "time_gap": gap, "where": list(map(str, where)),
            "compared": len(calls), "distinct": len(want)}


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    device: Optional[Dict] = None,
    simulate=None,
) -> Dict:
    """One run.  The tests and ``bench/tools/control.py`` stand in
    ``device`` and ``simulate``."""
    on_chip = device is None
    if on_chip:
        device = require_chip(cell.chips)
        use_checkout_cache()
    if simulate is None:
        from repro.core import simulate

    stream = traffic.CallStream(cell.config, cell.mix, seed)
    call_simulate(simulate, next(stream))  # warm-up: first-call costs
    probe = make_probe() if on_chip else (lambda: None)
    setup_s = time.perf_counter() - t_start

    annotate = contextlib.nullcontext
    if trace:
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        annotate = TraceAnnotation
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    gclock = GcClock()
    gc.callbacks.append(gclock)
    try:
        with annotate("bench.window"):
            with annotate("bench.probe"):
                probe()
            calls, failed, window_s = run_window(simulate, stream, seconds,
                                                 annotate)
    finally:
        gc.callbacks.remove(gclock)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = xtrace.reduce_planes(
            xtrace.load(xtrace.newest_xplane(str(TRACE_DIR))))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device = {**device, "busy_s": reduced["busy_s"],
                  "window_s": reduced["window_s"]}
    device = {**device,
              "memory_peak_bytes": _memory_peak() if on_chip else 0}

    win = Window(calls, window_s, setup_s, reduced)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = m.read(win)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    reasons = Counter(str(c["report"].meta.get("lockstep_reason"))
                      for c in calls if c["report"].meta.get("closed_loop"))
    print(f"calls {len(calls)} in {window_s} s; lockstep_reason per call: "
          f"{dict(reasons) or 'none (open loop)'}", file=sys.stderr)
    print(f"gc in the window: collections {dict(gclock.count)}, seconds "
          f"{dict(gclock.seconds)}", file=sys.stderr)

    t_ref = time.perf_counter()
    checked = check_answers(calls)
    t_ref = time.perf_counter() - t_ref
    checks = {
        "counts_off": {"value": checked["counts_off"],
                       "limit": COUNTS_OFF_LIMIT},
        "time_gap": {"value": checked["time_gap"], "limit": TIME_GAP_LIMIT},
        "failed_calls": {"value": failed, "limit": FAILED_CALLS_LIMIT},
    }
    correct = bool(calls) and all(
        c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": correct,
        "attempted": len(calls) + failed,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    print(f"compared all {checked['compared']} calls ({checked['distinct']} "
          f"distinct) with the reference in {t_ref:.3f} s; first field off "
          f"or widest gap: {checked['where'] or 'none'}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    return result


def _memory_peak() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
