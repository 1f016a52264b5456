"""Plain reference for the fused GEMV+AllReduce on one detailed GPU.

The Eidola paper's kernel (Fig. 3, Table 1), worked out workgroup by
workgroup from its definition, with no event queue, no write tracking
table and no monitor structure.  It imports nothing of the simulator.

The target GPU (device 0) runs ``workgroups`` workgroups; workgroup ``w``
sits on CU ``w % n_cus`` and is dispatched ``(w // n_cus) *
dispatch_stagger_cycles`` cycles after launch.  Its phases, in order:

  remote_tiles  one row time per peer-owned output row it holds
  flag_write    ``flag_write_cycles`` per peer
  local_tiles   one row time per locally owned row it holds
  wait_flags    observe every peer's flag, peers in ascending order
  reduce        ``reduce_cycles_per_row`` per local row
  broadcast     ``broadcast_cycles_per_row`` per local row

Rows are dealt round robin over the workgroups.  Each GPU holds ``K / n``
columns of A (all ``K`` under ``weak_scaling``).  A row time is the larger
of the compute time and the sector-streaming time of one such slice, with
the CU's throughput shared evenly by its workgroups, rounded up to a cycle.

Each peer writes its partials for the target's rows (if
``include_data_writes``), then its flag at its flag delay; a write becomes
visible at the target ``xgmi_enact_latency_ns`` after it is issued,
rounded to the nearest cycle.

Waiting on a flag that becomes visible at cycle ``V``, from cycle ``t``:

* spin: poll every ``poll_interval_cycles`` until a poll at or after ``V``
  sees it set; every poll is one flag read, and the observing one costs
  ``flag_check_cycles``;
* SyncMon: one check read.  If the flag is set, go on after
  ``flag_check_cycles``.  If not, arm a monitor (``monitor_arm_cycles``)
  and mwait.  A flag that lands before the monitor is armed makes the
  mwait return at once, with one more read.  Otherwise the write wakes the
  workgroup ``wake_latency_cycles`` later, and it resumes after a jitter
  of ``w % requeue_jitter_mod`` cycles.  The wake's validation reads are
  shared: workgroups woken in the same cycle on the same CU read in
  groups of ``wake_coalesce_width``.  Every write to a flag on which some
  monitor was armed before it counts as checked by the monitor.

``real`` is the number type of every time computed in ns (a lower
precision there is the check's control).
"""

from __future__ import annotations

import math
from fractions import Fraction
from typing import Callable, Dict, List, Sequence, Tuple

FLAG_BYTES = 8
N_TRAFFIC = ("flag_reads", "nonflag_reads", "total_reads", "local_writes",
             "xgmi_writes_in", "xgmi_writes_out", "xgmi_bytes_in",
             "xgmi_bytes_out", "read_bytes", "write_bytes")


def k_slice(c: Dict) -> int:
    """Columns of A each GPU holds: ``K`` split over the GPUs, or all of
    ``K`` on each under weak scaling."""
    return c["K"] if c["weak_scaling"] else c["K"] // (c["n_egpus"] + 1)


def row_cycles(c: Dict) -> int:
    ks = k_slice(c)
    sectors = -(-ks * c["elem_bytes"] // c["sector_bytes"])
    compute = Fraction(ks * c["N"] * c["workgroups"],
                       1) / Fraction(c["macs_per_cycle_per_cu"]) / c["n_cus"]
    memory = Fraction(sectors * c["workgroups"],
                      1) / Fraction(c["sectors_per_cycle_per_cu"]) / c["n_cus"]
    return max(1, math.ceil(max(compute, memory)))


def _visible_cycle(c: Dict, issue_ns, real: Callable) -> int:
    return int(round(real(real(issue_ns) + real(c["xgmi_enact_latency_ns"]))
                     * real(c["clock_ghz"])))


def peer_writes(c: Dict, delays: Sequence[float],
                real: Callable) -> List[Tuple[int, int, bool]]:
    """``(visible_cycle, peer, is_flag)`` of every peer write."""
    rows = c["M"] // (c["n_egpus"] + 1)
    out = []
    for g, d in enumerate(delays, start=1):
        d = real(d)
        if c["include_data_writes"]:
            lead = real(c["data_write_lead_ns"])
            t0 = max(real(0.0), d - lead)
            span = max(real(1.0), lead * real(0.5))
            for r in range(rows):
                t = min(t0 + span * real(r + 1) / real(rows), max(real(0.0), d))
                out.append((_visible_cycle(c, t, real), g, False))
        out.append((_visible_cycle(c, d, real), g, True))
    return out


def answer(call: Dict, *, real: Callable = float) -> Dict[Tuple, float]:
    """One call's answer; ``call["sim_config"]`` holds every model setting
    by name, ``call["params"]["flag_delays_ns"]`` one wakeupTime for every
    peer or one per peer."""
    params = call["params"]
    if call["scenario"] != "gemv_allreduce" or set(params) != {"flag_delays_ns"}:
        raise NotImplementedError(
            f"no reference for {call['scenario']} with {sorted(params)}")
    c = call["sim_config"]
    delays = params["flag_delays_ns"]
    if isinstance(delays, (int, float)):
        delays = [delays] * c["n_egpus"]
    return run(c, delays, real=real)


def run(c: Dict, flag_delays_ns: Sequence[float], *,
        real: Callable = float) -> Dict[Tuple, float]:
    """Every counter of one launch, keyed as the benchmark compares them."""
    n_dev = c["n_egpus"] + 1
    peers = c["n_egpus"]
    if len(flag_delays_ns) != peers:
        raise ValueError(f"need {peers} flag delays")
    if c["sync"] not in ("spin", "syncmon"):
        raise ValueError(f"unknown sync {c['sync']!r}")
    if c["sync"] == "syncmon" and c["monitor_semantics"] != "mesa":
        raise ValueError("the reference models mesa monitor semantics only")
    rows_local = c["M"] // n_dev
    rows_remote = c["M"] - rows_local
    spr = -(-k_slice(c) * c["elem_bytes"] // c["sector_bytes"])
    rc = row_cycles(c)
    data_bytes = c["elem_bytes"] * c["N"]
    wgs = c["workgroups"]
    syncmon = c["sync"] == "syncmon"

    writes = peer_writes(c, flag_delays_ns, real)
    visible = {g: v for v, g, is_flag in writes if is_flag}
    t_check, poll = c["flag_check_cycles"], c["poll_interval_cycles"]

    flag_reads = nonflag = local_w = xout = 0
    read_bytes = write_bytes = xout_bytes = 0
    armed = immediate = 0
    armed_on = set()
    wake_groups: Dict[Tuple[int, int], int] = {}
    kernel_end = 0
    for w in range(wgs):
        rr = rows_remote // wgs + (w < rows_remote % wgs)
        lr = rows_local // wgs + (w < rows_local % wgs)
        cu = w % c["n_cus"]
        t = (w // c["n_cus"]) * c["dispatch_stagger_cycles"]
        t += rr * rc + peers * c["flag_write_cycles"] + lr * rc
        for g in range(1, n_dev):
            v = visible[g]
            if not syncmon:
                polls = -(-(v - t) // poll) if v > t else 0
                flag_reads += polls + 1
                t += polls * poll + t_check
                continue
            flag_reads += 1  # the check
            if v <= t:
                t += t_check
                continue
            armed += 1
            armed_on.add(g)
            t_arm = t + c["monitor_arm_cycles"]
            if v <= t_arm:  # landed before the monitor was armed
                flag_reads += 1
                immediate += 1
                t = t_arm + t_check
                continue
            wake = v + c["wake_latency_cycles"]
            wake_groups[(wake, cu)] = wake_groups.get((wake, cu), 0) + 1
            t = wake + w % c["requeue_jitter_mod"] + t_check
        t += lr * c["reduce_cycles_per_row"] + lr * c["broadcast_cycles_per_row"]
        kernel_end = max(kernel_end, t)

        reduce_reads = lr * n_dev
        nonflag += (rr + lr) * spr + reduce_reads
        read_bytes += (rr + lr) * spr * c["sector_bytes"]
        read_bytes += reduce_reads * c["elem_bytes"]
        local_w += 2 * lr
        write_bytes += 2 * lr * data_bytes
        xout += rr + peers + lr * peers
        xout_bytes += (rr + lr * peers) * data_bytes + peers * FLAG_BYTES
    width = c["wake_coalesce_width"]
    flag_reads += sum(-(-n // width) for n in wake_groups.values())
    read_bytes += FLAG_BYTES * flag_reads

    n_in = len(writes)
    bytes_in = sum(FLAG_BYTES if is_flag else min(8, data_bytes)
                   for _, _, is_flag in writes)
    traffic = {
        "flag_reads": flag_reads, "nonflag_reads": nonflag,
        "total_reads": flag_reads + nonflag, "local_writes": local_w,
        "xgmi_writes_in": n_in, "xgmi_writes_out": xout,
        "xgmi_bytes_in": bytes_in, "xgmi_bytes_out": xout_bytes,
        "read_bytes": read_bytes, "write_bytes": write_bytes,
    }
    clock = real(c["clock_ghz"])
    out: Dict[Tuple, float] = {
        ("flag_reads",): flag_reads,
        ("nonflag_reads",): nonflag,
        ("wtt_registered",): n_in,
        ("wtt_enacted",): n_in,
        ("sim_cycles",): max(kernel_end, max(v for v, _, _ in writes)),
        ("kernel_span_ns",): float(real(kernel_end) / clock),
    }
    if syncmon:
        flags_checked = sum(1 for v, g, is_flag in writes
                            if is_flag and g in armed_on)
        out.update({
            ("monitor", "monitors_armed"): armed,
            ("monitor", "mwaits"): armed,
            ("monitor", "wakes"): armed,  # its flag's one write wakes each
            ("monitor", "immediate_mwait_returns"): immediate,
            ("monitor", "writes_checked"): flags_checked,
        })
    for k in N_TRAFFIC:
        out[("traffic", k)] = traffic[k]
        out[("device", 0, k)] = traffic[k]
    return out
