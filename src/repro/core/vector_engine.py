"""Vectorized batch-replay engine.

The TPU-idiomatic rethink of the WTT poll loop (DESIGN.md §2): because
eidolons are *replay-only*, their write times are independent of target-device
state, so every workgroup's wait behaviour is a pure function of (its phase
schedule, the flag visibility times).  That turns the simulator's inner loop —
a pointer-chasing priority queue polled per cycle in gem5 — into a handful of
dense array passes over all workgroups at once.  Results are bit-identical to
the cycle/event engines (asserted in tests); wall time is near-constant in
simulated cycles and sub-linear in everything else.

This engine is replay-only and gemv-specific; the same closed forms applied
to the N-device closed loop live in ``repro.core.cohort_timeline`` (lanes)
and ``repro.core.lockstep`` (all ranks × all loop steps of a symbolic
program, advanced in bulk without unrolling).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

from .config import SimConfig, SyncPolicy
from .events import RegisteredWrite, Segment, effective_writes
from .spans import span

__all__ = ["run_vectorized"]


def _effective_writes(sim) -> List[RegisteredWrite]:
    return effective_writes(
        sim.traces,
        latency_ns=sim.cfg.xgmi_enact_latency_ns,
        perturb=sim.perturb,
    )


def run_vectorized(sim) -> "Report":  # noqa: F821 - avoids circular import
    from .simulator import Report
    from .workload import GemvAllReduceWorkload

    with span("engine.run") as run:
        cfg: SimConfig = sim.cfg
        workload = GemvAllReduceWorkload(cfg, sim.amap)
        plans = workload.plans
        nwg = len(plans)
        order = workload.flag_order()

        writes = _effective_writes(sim)

        # Flag visibility cycles: first write to each (src_device, slot) wins.
        # Resolution uses amap.decode_flag — O(1) per write and covering EVERY
        # flag slot — rather than comparing against the slot-0 addresses only:
        # a multi-slot trace bundle (ring steps, pipeline microbatches) would be
        # invisible to a slot-0 linear scan and the run misreported as a
        # "no flag writes" deadlock even though the bundle is full of flags.
        flag_T: Dict[tuple, int] = {}
        for w in sorted(writes, key=lambda w: (w.wakeup_ns, w.seq)):
            decoded = sim.amap.decode_flag(w.addr)
            if decoded is not None and decoded not in flag_T:
                flag_T[decoded] = cfg.ns_to_cycles(w.wakeup_ns)
        # the gemv workload polls each peer's slot-0 flag, in flag_order()
        missing = [g for g in order if (g, 0) not in flag_T]
        if missing:
            from .target import EidolaDeadlock

            have = sorted(flag_T)
            raise EidolaDeadlock(
                f"no slot-0 flag writes for peers {missing} in trace"
                + (
                    f" (bundle carries flags for (src, slot) {have})"
                    if have
                    else ""
                )
            )

        # --- per-WG static schedule (perturbable) -------------------------------
        def dur(wg_i: int, state: str, base: int) -> int:
            if sim.perturb is not None and base > 0:
                return sim.perturb.scale_phase(wg_i, state, base)
            return base

        dispatch = np.array([p.dispatch_cycle for p in plans], dtype=np.int64)
        remote = np.array(
            [dur(p.wg, "remote_tiles", p.remote_cycles) for p in plans], dtype=np.int64
        )
        flagw = np.array(
            [dur(p.wg, "flag_write", p.flag_write_cycles) for p in plans], dtype=np.int64
        )
        local = np.array(
            [dur(p.wg, "local_tiles", p.local_cycles) for p in plans], dtype=np.int64
        )
        reduce_d = np.array(
            [dur(p.wg, "reduce", p.reduce_cycles) for p in plans], dtype=np.int64
        )
        bcast_d = np.array(
            [dur(p.wg, "broadcast", p.broadcast_cycles) for p in plans], dtype=np.int64
        )
        cu = np.array([p.cu for p in plans], dtype=np.int64)
        wg_idx = np.arange(nwg, dtype=np.int64)

        wait_start = dispatch + remote + flagw + local
        c = wait_start.copy()
        flag_reads = np.zeros(nwg, dtype=np.int64)
        poll = cfg.poll_interval_cycles
        check = cfg.flag_check_cycles
        arm = cfg.monitor_arm_cycles
        wl = cfg.wake_latency_cycles
        jit = wg_idx % max(1, cfg.requeue_jitter_mod)

        coalesce_groups: Dict[Tuple[int, int], int] = {}
        monitor_stats = {
            "monitors_armed": 0,
            "mwaits": 0,
            "wakes": 0,
            "immediate_mwait_returns": 0,
            "writes_checked": 0,
        }
        desched: List[Tuple[int, int, int]] = []  # (wg, t_arm, wake_c)

        for g in order:
            T = flag_T[(g, 0)]
            already = T <= c
            if cfg.sync == SyncPolicy.SPIN:
                nticks = np.where(
                    already, 0, np.ceil(np.maximum(T - c, 0) / poll).astype(np.int64)
                )
                flag_reads += np.where(already, 1, nticks + 1)
                c = np.where(already, c + check, c + nticks * poll + check)
            else:
                flag_reads += 1  # check/observe read
                t_arm = c + arm
                race = (~already) & (T <= t_arm)
                blocked = (~already) & (T > t_arm)
                flag_reads += race.astype(np.int64)
                # coalesced wake-validation accounting
                wake_c = T + wl
                for cu_id in range(cfg.n_cus):
                    n = int(np.sum(blocked & (cu == cu_id)))
                    if n:
                        coalesce_groups[(wake_c, cu_id)] = (
                            coalesce_groups.get((wake_c, cu_id), 0) + n
                        )
                nblocked = int(blocked.sum())
                nrace = int(race.sum())
                monitor_stats["monitors_armed"] += nblocked + nrace
                monitor_stats["mwaits"] += nblocked + nrace
                monitor_stats["wakes"] += nblocked + nrace
                monitor_stats["immediate_mwait_returns"] += nrace
                if nblocked:
                    monitor_stats["writes_checked"] += 1
                for i in np.nonzero(blocked)[0]:
                    desched.append((int(i), int(t_arm[i]), wake_c))
                resume = wake_c + jit
                c = np.where(
                    already,
                    c + check,
                    np.where(race, t_arm + check, resume + check),
                )

        coalesced_reads = sum(
            math.ceil(n / max(1, cfg.wake_coalesce_width))
            for n in coalesce_groups.values()
        )
        total_flag_reads = int(flag_reads.sum()) + coalesced_reads

        wait_end = c
        reduce_end = wait_end + reduce_d
        bcast_end = reduce_end + bcast_d
        kernel_end = int(bcast_end.max()) if nwg else 0
        # writes beyond kernel end still enact (drained), matching event engine
        last_write_cycle = max(
            (cfg.ns_to_cycles(w.wakeup_ns) for w in writes), default=0
        )
        sim_cycles = max(kernel_end, last_write_cycle)

        # --- closed-form non-flag traffic ---------------------------------------
        nonflag = sum(
            p.remote_sector_reads + p.local_sector_reads + p.reduce_reads for p in plans
        )
        sector_reads = sum(p.remote_sector_reads + p.local_sector_reads for p in plans)
        reduce_reads = sum(p.reduce_reads for p in plans)
        local_writes = sum(
            p.local_partial_writes + p.broadcast_local_writes for p in plans
        )
        xgmi_out = sum(
            p.remote_xgmi_writes + p.broadcast_xgmi_writes for p in plans
        ) + nwg * len(order)
        xgmi_out_bytes = (
            sum(p.remote_xgmi_writes + p.broadcast_xgmi_writes for p in plans)
            * cfg.elem_bytes
            * cfg.N
            + nwg * len(order) * 8
        )
        traffic = {
            "flag_reads": total_flag_reads,
            "nonflag_reads": nonflag,
            "total_reads": total_flag_reads + nonflag,
            "local_writes": local_writes,
            "xgmi_writes_in": len(writes),
            "xgmi_writes_out": xgmi_out,
            "xgmi_bytes_in": sum(w.size for w in writes),
            "xgmi_bytes_out": xgmi_out_bytes,
            "read_bytes": sector_reads * cfg.sector_bytes
            + reduce_reads * cfg.elem_bytes
            + total_flag_reads * 8,
            "write_bytes": local_writes * cfg.elem_bytes * cfg.N,
        }

        segments: List[Segment] = []
        if sim.collect_segments:
            ns = cfg.cycles_to_ns
            for i, p in enumerate(plans):
                t = int(dispatch[i])
                bounds = [
                    ("remote_tiles", t, t + int(remote[i])),
                    ("flag_write", t + int(remote[i]), t + int(remote[i]) + int(flagw[i])),
                    (
                        "local_tiles",
                        t + int(remote[i]) + int(flagw[i]),
                        int(wait_start[i]),
                    ),
                    ("wait_flags", int(wait_start[i]), int(wait_end[i])),
                    ("reduce", int(wait_end[i]), int(reduce_end[i])),
                    ("broadcast", int(reduce_end[i]), int(bcast_end[i])),
                ]
                for name, s, e in bounds:
                    segments.append(
                        Segment(wg=p.wg, phase=name, start_ns=ns(s), end_ns=ns(e))
                    )
            for wg_i, t_arm_i, wake_c in desched:
                segments.append(
                    Segment(
                        wg=plans[wg_i].wg,
                        phase="descheduled",
                        start_ns=ns(t_arm_i),
                        end_ns=ns(wake_c),
                    )
                )
            segments.sort(key=lambda s: (s.wg, s.start_ns))

    return Report(
        engine="vector",
        sync=cfg.sync.value,
        traffic=traffic,
        flag_reads=total_flag_reads,
        nonflag_reads=nonflag,
        kernel_span_ns=cfg.cycles_to_ns(kernel_end),
        sim_cycles=sim_cycles,
        wall_time_s=run.dur,
        wtt_registered=len(writes),
        wtt_enacted=len(writes),
        wtt_head_polls=0,
        monitor_stats=monitor_stats if cfg.sync == SyncPolicy.SYNCMON else {},
        segments=segments,
        meta=dict(sim.traces.meta),
        n_devices=1,
        per_device={0: dict(traffic)},
        closed_loop=False,
    )
