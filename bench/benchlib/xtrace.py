"""Reduce a profiler trace to device busy time, idle share and a breakdown.

The traced window is the host span named ``bench.window``.  Device
operations are the events on the ``XLA Ops`` line of every ``/device:TPU:``
plane; a chip's busy time is the union of its operations' intervals inside
the window, and ``busy_s`` averages it over the chips in the trace.  The
idle share is ``1 - busy_s / window_s``: exactly 1.0 when no operation ran.

The device planes' clock is not the host's: on a TPU v5e the probe's
operations read about a millisecond before the host span that launched
them.  The window's first device work is the probe, which cannot start
before its host span ``bench.probe`` does, so the device timeline is
shifted forward by however much its first operation leads that span.

The breakdown lists the ten device operations that took most time, and
the ten longest idle gaps, each cut by the host span it lies under
(``simulate`` for a call of the simulator, ``bench.*`` for the harness,
``host.other`` outside any of them).

:func:`load` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
(no accelerator library is loaded); :func:`reduce_planes` works on the
plain lists it returns, so it can be tested on any recorded trace.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
PROBE = "bench.probe"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
TOP = 10

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
Plane = Tuple[str, List[Tuple[str, List[Event]]]]


def newest_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        (plane.name,
         [(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events])
          for line in plane.lines])
        for plane in data.planes
    ]


def _union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce_planes(planes: List[Plane]) -> Dict:
    host_spans: List[Event] = []
    window: Optional[Tuple[float, float]] = None
    probe_start: Optional[float] = None
    chips: List[List[Event]] = []
    for name, lines in planes:
        if name.startswith(DEVICE_PLANE):
            chips.append([ev for ln, evs in lines if ln == OPS_LINE
                          for ev in evs])
            continue
        if not name.startswith("/host:"):
            continue
        for _, evs in lines:
            for ev in evs:
                if ev[0] == WINDOW:
                    if window is None or ev[2] > window[1] - window[0]:
                        window = (ev[1], ev[1] + ev[2])
                elif ev[0] == "simulate" or ev[0].startswith("bench."):
                    host_spans.append(ev)
                    if ev[0] == PROBE and (probe_start is None
                                           or ev[1] < probe_start):
                        probe_start = ev[1]
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} host span")
    lo, hi = window
    window_s = (hi - lo) * 1e-9
    first = min((s for ops in chips for _, s, _ in ops), default=None)
    shift = 0.0
    if first is not None and probe_start is not None and first < probe_start:
        shift = probe_start - first

    per_chip_busy = []
    op_time: Dict[str, float] = {}
    all_ops: List[Tuple[float, float]] = []
    for ops in chips:
        clipped = []
        for n, s, d in ops:
            iv = _clip(s + shift, s + shift + d, lo, hi)
            if iv is None:
                continue
            clipped.append(iv)
            n = n.split(" = ")[0].lstrip("%")  # HLO instruction name
            op_time[n] = op_time.get(n, 0.0) + (iv[1] - iv[0]) * 1e-9
        per_chip_busy.append(sum(e - s for s, e in _union(clipped)) * 1e-9)
        all_ops.extend(clipped)
    busy_s = sum(per_chip_busy) / max(1, len(per_chip_busy))

    # idle gaps of the window (no op on any chip), cut by host span
    gaps, t = [], lo
    for s, e in _union(all_ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted((s, s + d, n) for n, s, d in host_spans)
    pieces: List[Tuple[str, float]] = []
    for gs, ge in gaps:
        cursor = gs
        for s, e, n in spans:
            iv = _clip(s, e, cursor, ge)
            if iv is None:
                continue
            if iv[0] > cursor:
                pieces.append(("host.other", (iv[0] - cursor) * 1e-9))
            pieces.append((n, (iv[1] - iv[0]) * 1e-9))
            cursor = iv[1]
        if ge > cursor:
            pieces.append(("host.other", (ge - cursor) * 1e-9))
    pieces.sort(key=lambda p: -p[1])
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "chips": len(chips),
        "clock_shift_s": shift * 1e-9,
        "device_ops": sorted(([n, v] for n, v in op_time.items()),
                             key=lambda p: -p[1])[:TOP],
        "idle_gaps": [[n, v] for n, v in pieces[:TOP]],
    }
