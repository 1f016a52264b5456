"""Shared arithmetic of the pod references, written from the simulator's
documented model and not from its code.  Imports nothing of ``repro``.

What a closed-loop collective on a pod does, as the reference models it:

* **Workgroups.**  Every rank launches ``workgroups`` workgroups; workgroup
  ``w`` sits on CU ``w % n_cus`` and starts ``(w // n_cus) *
  dispatch_stagger_cycles`` cycles after launch, so the workgroups of one
  dispatch wave run in step.  A timed phase takes a whole number of
  cycles: the sectors of one workgroup's share, streamed at its even share
  of the CUs' sector throughput, rounded up.
* **Spin waits.**  A wave waiting from cycle ``t`` on a flag that becomes
  visible at cycle ``V`` polls every ``poll_interval_cycles``; each poll is
  one 8-byte flag read per workgroup, the poll that sees the flag set
  included, and the wave goes on ``flag_check_cycles`` after it.  Flags of
  one wait phase are observed one after another.
* **Emission.**  A phase that emits a flag emits it once per rank, when its
  last wave finishes the phase.  The flag rides behind the phase's payload
  (``payload + 8`` bytes) over the fabric and lands, with ``writes_per_step``
  8-byte marker writes ahead of it, in the destination's write tracking
  table ``xgmi_enact_latency_ns`` after it arrives; it is never visible in
  the cycle that emitted it.  A time in ns becomes a cycle by rounding half
  to even.
* **Fabric (rail optimized).**  Nodes of ``devices_per_node`` devices; the
  devices of a node form a bidirectional ring (shortest way, ties going up).
  A message to another node rides the destination's rail (its local rank):
  over the source node's ring to the device that owns that rail's NIC, over
  the rail in one hop, then over the destination node's ring.  Each leg
  waits for its egress port (first come, first served, behind the port's
  previous burst), serializes at its class's bandwidth and then propagates
  ``hops`` hop latencies.  A port is keyed by ``(device, direction)`` on a
  node's ring and by ``(node, rail)`` on a rail.

This module keeps the reference's fabric state and counters; each scenario's
reference (``ring_allreduce.py``, ``hierarchical_allreduce.py``) walks its
collective's steps with it, vectorized over the ranks that take a step
together.  ``real`` is the number type of every time in ns: ``np.float64``,
or a lower precision for the check's control.
"""

from __future__ import annotations

from fractions import Fraction
from typing import Dict, List, Optional, Tuple

import numpy as np

FLAG_BYTES = 8
TRAFFIC = ("flag_reads", "nonflag_reads", "total_reads", "local_writes",
           "xgmi_writes_in", "xgmi_writes_out", "xgmi_bytes_in",
           "xgmi_bytes_out", "read_bytes", "write_bytes")


def phase_cycles(nbytes: int, c: Dict) -> Tuple[int, int, int]:
    """``(share, sectors, cycles)`` of one workgroup's slice of an
    ``nbytes`` block: at least one byte, whole sectors, and the cycles to
    stream them at the workgroup's share of the CUs' sector throughput."""
    share = max(1, nbytes // c["workgroups"])
    sectors = -(-share // c["sector_bytes"])
    per_cycle = Fraction(c["sectors_per_cycle_per_cu"]) * c["n_cus"] / c["workgroups"]
    cycles = -(-Fraction(sectors) // per_cycle)
    return share, sectors, max(1, int(cycles))


def waves(c: Dict) -> List[Tuple[int, int]]:
    """``(members, dispatch_cycle)`` of each dispatch wave."""
    out = []
    w, n_cus = c["workgroups"], c["n_cus"]
    for j in range(-(-w // n_cus)):
        out.append((min(n_cus, w - j * n_cus), j * c["dispatch_stagger_cycles"]))
    return out


class Pod:
    """Fabric state and every counter of one closed-loop run.

    ``cursor`` holds each wave's cycle on each rank (shape waves x ranks).
    """

    def __init__(self, c: Dict, hw: Dict, n: int, dpn: int, *,
                 writes_per_step: int, real=np.float64):
        if c["sync"] != "spin":
            raise NotImplementedError("the pod references model spin waits")
        if n % dpn:
            raise ValueError(f"devices_per_node={dpn} must divide {n}")
        self.c, self.n, self.dpn, self.nodes = c, n, dpn, n // dpn
        self.real = real
        self.clock = real(c["clock_ghz"])
        self.enact = real(c["xgmi_enact_latency_ns"])
        self.marks = writes_per_step if c["include_data_writes"] else 0
        # link classes: bytes per ns and ns per hop
        self.cls = {
            "ici": (real(hw["ici_link_bw"] * hw["ici_links_per_axis"] / 1e9),
                    real(hw["ici_hop_latency_s"] * 1e9)),
            "rail": (real(hw["dci_link_bw"] / 1e9),
                     real(hw["dci_hop_latency_s"] * 1e9)),
        }
        # port index: ring ports 2*dev (+1) and 2*dev+1 (-1), rails after
        self.busy = np.zeros(2 * n + self.nodes * dpn, dtype=real)
        self.port_owner = np.full(self.busy.size, -1, dtype=np.int64)
        self.wave = waves(c)
        self.counts = np.array([m for m, _ in self.wave], dtype=np.int64)
        self.cursor = np.array([[d] * n for _, d in self.wave], dtype=np.int64)
        self.t = {k: np.zeros(n, dtype=np.int64) for k in TRAFFIC}
        self.flags_seen = 0  # latest visible cycle of any write
        self.emissions = 0
        self.bytes = 0
        self.leg_msgs = {"ici": 0, "rail": 0}
        self.leg_bytes = {"ici": 0, "rail": 0}
        # queued time of every leg that waited, keyed for the order in
        # which the simulator adds them: (cycle, source, seq, leg)
        self._queued: List[Tuple[np.ndarray, ...]] = []
        self._seq = np.zeros(n, dtype=np.int64)
        self.routes: List[Route] = []
        # per-workgroup traffic of the timed phases, summed per rank set
        self._tally: Dict[int, list] = {}

    # -- routing ----------------------------------------------------------

    def _ring_leg(self, src_dev, a, b):
        """Port index and hops of the shortest way from local rank ``a`` to
        ``b`` on the node ring, leaving from device ``src_dev``."""
        fwd = (b - a) % self.dpn
        bwd = (a - b) % self.dpn
        up = fwd <= bwd
        return 2 * src_dev + np.where(up, 0, 1), np.where(up, fwd, bwd)

    def route(self, src: np.ndarray, dst: np.ndarray) -> "Route":
        """The legs of each ``src -> dst`` message; every port serves one
        source device over the whole run, which is what lets the reference
        price each port in its source's own order."""
        dpn = self.dpn
        sn, sl = np.divmod(src, dpn)
        dn, dl = np.divmod(dst, dpn)
        same = sn == dn
        rail = dl % dpn  # one NIC per local rank: rails == devices_per_node
        legs = []
        # leg 0: on the source node's ring (to dst, or to the rail's NIC)
        to = np.where(same, dl, rail)
        p0, h0 = self._ring_leg(src, sl, to)
        legs.append(("ici", (same | (sl != rail)) & (src != dst), p0, h0))
        # leg 1: the rail
        p1 = 2 * self.n + sn * dpn + rail
        legs.append(("rail", ~same, p1, np.ones_like(src)))
        # leg 2: on the destination node's ring, from the rail's NIC
        p2, h2 = self._ring_leg(dn * dpn + rail, rail, dl)
        legs.append(("ici", ~same & (dl != rail), p2, h2))
        used = np.concatenate([p[m] for _, m, p, _ in legs])
        owner = np.concatenate([src[m] for _, m, _, _ in legs])
        if np.unique(used).size != used.size:
            raise NotImplementedError("two messages of one step share a port")
        prev = self.port_owner[used]
        if np.any((prev >= 0) & (prev != owner)):
            raise NotImplementedError(
                "a port serves two source devices; the reference prices "
                "ports in their source's order only")
        self.port_owner[used] = owner
        route = Route(src, dst, [
            (name, np.nonzero(m)[0], p[m],
             h[m].astype(self.real) * self.cls[name][1])
            for name, m, p, h in legs if m.any()])
        self.routes.append(route)
        return route

    # -- time -------------------------------------------------------------

    def emit(self, route: "Route", cycle: np.ndarray, payload: int) -> np.ndarray:
        """Send each source's flag at ``cycle`` (its last wave's phase end);
        return the cycle at which it becomes visible at the destination."""
        real, clock = self.real, self.clock
        nbytes = payload + FLAG_BYTES
        t = cycle.astype(real) / clock
        for j, (name, i, port, hop_ns) in enumerate(route.legs):
            bw = self.cls[name][0]
            ready = t[i]
            start = np.maximum(ready, self.busy[port])
            ser = real(nbytes) / bw
            self.busy[port] = start + ser
            t[i] = start + ser + hop_ns
            self.leg_msgs[name] += i.size
            self.leg_bytes[name] += i.size * nbytes
            q = start - ready
            if q.any():
                w = np.nonzero(q)[0]
                self._queued.append((cycle[i[w]], route.src[i[w]],
                                     self._seq[route.src[i[w]]],
                                     np.full(w.size, j), q[w], name))
        self._seq[route.src] += 1
        floor = (cycle + 1).astype(real) / clock
        wake = t + self.enact
        wake = np.where(wake < floor, floor, wake)
        vis = np.rint(wake * clock).astype(np.int64)
        m = route.src.size
        self.emissions += m
        self.bytes += m * nbytes
        route.sent += 1
        self.flags_seen = max(self.flags_seen, int(vis.max(initial=0)))
        return vis

    def wait(self, ranks: np.ndarray, visible: np.ndarray) -> None:
        """The waves of ``ranks`` spin on one flag each, visible at
        ``visible`` (one per rank)."""
        c = self.c
        poll, check = c["poll_interval_cycles"], c["flag_check_cycles"]
        cur = self.cursor[:, ranks]
        polls = np.maximum(0, -((cur - visible) // poll))
        self.cursor[:, ranks] = cur + polls * poll + check
        reads = (self.counts[:, None] * (polls + 1)).sum(axis=0)
        self.t["flag_reads"][ranks] += reads
        self.t["read_bytes"][ranks] += FLAG_BYTES * reads

    def work(self, ranks: np.ndarray, cycles: int, *, reads: int = 0,
             sector_bytes: int = 0, local: int = 0, out: int = 0,
             nbytes: int = 0) -> np.ndarray:
        """A timed phase on ``ranks``: every workgroup streams ``reads``
        sectors, makes ``local`` local writes and ``out`` fabric writes of
        ``nbytes`` each.  Returns the cycle its last wave ends it."""
        self.cursor[:, ranks] += cycles
        tally = self._tally.setdefault(id(ranks), [ranks, 0, 0, 0, 0, 0, 0])
        for j, v in enumerate((reads, reads * sector_bytes, local,
                               local * nbytes, out, out * nbytes), start=1):
            tally[j] += v
        return self.cursor[:, ranks].max(axis=0)

    # -- the answer -------------------------------------------------------

    def answer(self) -> Dict[Tuple, float]:
        t = self.t
        w = int(self.counts.sum())
        for ranks, *vals in self._tally.values():
            for key, v in zip(("nonflag_reads", "read_bytes", "local_writes",
                               "write_bytes", "xgmi_writes_out",
                               "xgmi_bytes_out"), vals):
                np.add.at(t[key], ranks, v * w)
        for r in self.routes:
            # out of the source: the flag write; into the destination: the
            # markers and the flag
            np.add.at(t["xgmi_writes_out"], r.src, r.sent)
            np.add.at(t["xgmi_bytes_out"], r.src, FLAG_BYTES * r.sent)
            np.add.at(t["xgmi_writes_in"], r.dst, (1 + self.marks) * r.sent)
            np.add.at(t["xgmi_bytes_in"], r.dst,
                      FLAG_BYTES * (1 + self.marks) * r.sent)
        t["total_reads"] = t["flag_reads"] + t["nonflag_reads"]
        end = self.cursor.max(axis=0)
        spans = end.astype(self.real) / self.clock
        out: Dict[Tuple, float] = {}
        for k in TRAFFIC:
            out[("traffic", k)] = int(t[k].sum())
        out[("flag_reads",)] = out[("traffic", "flag_reads")]
        out[("nonflag_reads",)] = out[("traffic", "nonflag_reads")]
        out[("kernel_span_ns",)] = float(spans.max())
        out[("sim_cycles",)] = int(max(int(end.max()), self.flags_seen))
        writes = int(t["xgmi_writes_in"].sum())
        out[("wtt_registered",)] = writes
        out[("wtt_enacted",)] = writes
        for d in range(self.n):
            for k in TRAFFIC:
                out[("device", d, k)] = int(t[k][d])
            out[("span_ns", d)] = float(spans[d])
        out.update(self._fabric())
        return out

    def _fabric(self) -> Dict[Tuple, float]:
        out: Dict[Tuple, float] = {
            ("fabric", "messages"): self.emissions,
            ("fabric", "bytes"): self.bytes,
        }
        total = 0.0
        per = {"ici": 0.0, "rail": 0.0}
        if self._queued:
            cyc = np.concatenate([k[0] for k in self._queued])
            src = np.concatenate([k[1] for k in self._queued])
            seq = np.concatenate([k[2] for k in self._queued])
            leg = np.concatenate([k[3] for k in self._queued])
            q = np.concatenate([k[4] for k in self._queued])
            cls = np.concatenate([np.full(k[0].size, k[5] == "rail")
                                  for k in self._queued])
            # the simulator adds each leg's wait as messages are sent:
            # by cycle, then source device, then the source's own order
            order = np.lexsort((leg, seq, src, cyc))
            q, cls = q[order], cls[order]
            total = float(np.cumsum(np.concatenate(([0.0], q)).astype(self.real))[-1])
            for name, m in (("ici", ~cls), ("rail", cls)):
                if m.any():
                    per[name] = float(np.cumsum(
                        np.concatenate(([0.0], q[m])).astype(self.real))[-1])
        out[("fabric", "queued_ns")] = total
        for name in ("ici", "rail"):
            out[("fabric", f"{name}_messages")] = self.leg_msgs[name]
            out[("fabric", f"{name}_bytes")] = self.leg_bytes[name]
            out[("fabric", f"{name}_queued_ns")] = per[name]
        return out


class Route:
    """The legs of one step's messages: per leg, its class, the messages
    that take it, their port indexes and their propagation in ns."""

    def __init__(self, src: np.ndarray, dst: np.ndarray,
                 legs: List[Tuple[str, np.ndarray, np.ndarray, np.ndarray]]):
        self.src, self.dst, self.legs = src, dst, legs
        self.sent = 0  # times each of its messages was sent


def pod_of(call: Dict) -> Tuple[Dict, Dict, int, int, Optional[int]]:
    """``(sim_config, hardware, devices, devices_per_node, payload)`` of a
    pod call, with the fabric it names checked."""
    p = call["params"]
    if p.get("fabric") != "rail_optimized" or not p.get("closed_loop"):
        raise NotImplementedError(
            "the pod references model closed-loop rail_optimized runs")
    return (call["sim_config"], call["hardware"], int(p["devices"]),
            int(p["devices_per_node"]), int(p["payload_bytes"]))
