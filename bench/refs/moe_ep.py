"""Plain reference for one expert-parallel MoE layer (``moe_ep``) on a
rail-optimized pod.

Each rank holds ``tokens_per_device`` tokens; ``moe_routing`` gives the
pairs every rank sends every other.  With ``Db``/``Cb`` the dispatch and
combine bytes of one pair and a workgroup's share of ``x`` bytes ``max(1,
x // workgroups)`` streamed as whole sectors (``podlib.phase_cycles``):

1. **Dispatch**: every rank streams its share of ``tokens * top_k * Db``
   bytes, writes its share of the fabric-bound pairs' bytes out and of its
   own pairs' locally, then sends every peer, ascending, its pairs' bytes
   ahead of a flag.
2. **Barrier**: every rank spins on each peer's dispatch flag, ascending.
3. **Experts**: ``max(compute, weights)`` cycles: the MACs of its received
   pairs and its own ``tokens * shared_experts`` tokens (``3 * hidden *
   expert_width`` each) at ``macs_per_cycle_per_cu * n_cus`` a cycle, and
   the FP8 weights of its slots and shared experts at the CUs' sector rate,
   each rounded up.  Each workgroup streams its share of the weights and
   received pairs, and writes its share of the BF16 outputs locally.
4. **Combine**: streams its share of its received pairs' ``Cb`` bytes,
   writes the fabric-bound share out and its own pairs' locally, and sends
   each peer the pairs it received from that peer, times ``Cb``.
5. **Barrier** on each peer's combine flag, ascending.
6. **Reduce**: streams its share of ``tokens * top_k * Cb`` bytes and
   writes its share of ``tokens * 2 * hidden`` locally.

**Fabric.**  A rank's messages of one phase leave when its last wave ends
the phase.  Messages are priced first come, first served in the order the
simulator sends them: by that cycle, then by source rank, then by
destination.  Each takes its legs (``podlib``'s rail-optimized routing)
one after another, each leg waiting for its port behind every earlier
message's burst on it; ``Pod.emit`` cannot serve here because it assumes
one touch per port.  Visibility, spin waits and the counters are
``podlib``'s.  Imports nothing of ``repro``.
"""

from __future__ import annotations

import math
from fractions import Fraction
from typing import Dict, Tuple

import numpy as np

from moe_routing import pairs_of
from podlib import FLAG_BYTES, Pod, Route, phase_cycles


def leg_table(pod: Pod):
    """``legs[s][d]``: the ``(class, port, hops)`` of each leg of ``s ->
    d``.  In a node: the ring's shortest way.  Across nodes: the
    destination's rail (its local rank), reached over the source node's
    ring when the source does not own that rail's NIC, then the rail."""
    n, dpn = pod.n, pod.dpn
    dl = np.arange(n) % dpn
    legs = []
    for s in range(n):
        sn, sl = divmod(s, dpn)
        port, hops = pod._ring_leg(s, sl, dl)
        row = []
        for d in range(n):
            ring = ("ici", int(port[d]), int(hops[d]))
            if d // dpn == sn:
                row.append([ring] if d != s else [])
                continue
            rail = ("rail", 2 * n + sn * dpn + int(dl[d]), 1)
            row.append([ring, rail] if sl != dl[d] else [rail])
        legs.append(row)
    return legs


def fanout(pod: Pod, legs, cycle: np.ndarray,
           payload: np.ndarray) -> np.ndarray:
    """Every rank ``s`` sends every peer ``d`` ``payload[s, d]`` bytes and
    a flag at ``cycle[s]`` over ``legs[s][d]``; returns ``vis[s, d]``, the
    cycle at which the flag becomes visible at ``d``."""
    n, real = pod.n, pod.real
    R = float if real is np.float64 else real
    clock = R(pod.clock)
    busy = [R(b) for b in pod.busy]
    cls = {k: (R(bw), R(lat)) for k, (bw, lat) in pod.cls.items()}
    cycles = [int(x) for x in cycle]
    sizes = (payload + FLAG_BYTES).tolist()
    order = sorted(range(n), key=lambda s: (cycles[s], s))
    src, dst, arrive = [], [], []
    queued = {"ici": [], "rail": []}
    for s in order:
        t0 = R(cycles[s]) / clock
        for d in range(n):
            if d == s:
                continue
            nbytes = sizes[s][d]
            t = t0
            for j, (name, port, hops) in enumerate(legs[s][d]):
                bw, lat = cls[name]
                start = t if t > busy[port] else busy[port]
                busy[port] = start + R(nbytes) / bw
                if start != t:
                    queued[name].append((cycles[s], s, pod._seq[s], j,
                                         start - t))
                t = busy[port] + R(hops) * lat
                pod.leg_msgs[name] += 1
                pod.leg_bytes[name] += nbytes
            pod._seq[s] += 1
            src.append(s)
            dst.append(d)
            arrive.append(t)
            pod.emissions += 1
            pod.bytes += nbytes
    pod.busy[:] = busy
    for name, rows in queued.items():
        if rows:
            c, s, q, j, w = (np.array(x) for x in zip(*rows))
            pod._queued.append((c, s, q, j, w.astype(real), name))
    src = np.array(src, np.int64)
    dst = np.array(dst, np.int64)
    route = Route(src, dst, [])
    route.sent = 1
    pod.routes.append(route)
    t = np.array(arrive, dtype=real)
    floor = (cycle[src] + 1).astype(real) / pod.clock
    wake = t + pod.enact
    wake = np.where(wake < floor, floor, wake)
    vis = np.zeros((n, n), np.int64)
    vis[src, dst] = np.rint(wake * pod.clock).astype(np.int64)
    pod.flags_seen = max(pod.flags_seen, int(vis.max(initial=0)))
    return vis


def barrier(pod: Pod, ranks: np.ndarray, vis: np.ndarray) -> None:
    """Every rank spins on each peer's flag, writers ascending."""
    for j in range(pod.n - 1):
        writer = np.where(ranks > j, j, j + 1)
        pod.wait(ranks, vis[writer, ranks])


def account(pod: Pod, ranks, *, reads=0, local=(0, 0), out=(0, 0)) -> None:
    """Per-workgroup traffic of a timed phase, on every workgroup of each
    rank: ``reads`` sectors, ``local``/``out`` as (writes, bytes each)."""
    w = int(pod.counts.sum())
    sb = pod.c["sector_bytes"]
    t = pod.t
    for key, v in (("nonflag_reads", reads), ("read_bytes", reads * sb),
                   ("local_writes", local[0]),
                   ("write_bytes", local[0] * local[1]),
                   ("xgmi_writes_out", out[0]),
                   ("xgmi_bytes_out", out[0] * out[1])):
        t[key][ranks] += np.asarray(v, np.int64) * w


def _one_each(nbytes: np.ndarray, wg: int) -> Tuple[np.ndarray, np.ndarray]:
    """(writes, bytes each) of a workgroup's share: one write of
    ``nbytes // wg`` where that is not zero."""
    share = nbytes // wg
    return (share > 0).astype(np.int64), share


def answer(call: Dict, *, real=np.float64) -> Dict[Tuple, float]:
    p, c, hw = call["params"], call["sim_config"], call["hardware"]
    if p.get("fabric") != "rail_optimized" or not p.get("closed_loop"):
        raise NotImplementedError(
            "the moe_ep reference models closed-loop rail_optimized runs")
    n, dpn = int(p["devices"]), int(p["devices_per_node"])
    pod = Pod(c, hw, n, dpn, real=real,
              writes_per_step=int(p["writes_per_peer"]))
    pairs, slot_rank = pairs_of(p, n)
    legs = leg_table(pod)
    hidden = int(p["hidden"])
    tokens = int(p["tokens_per_device"])
    top_k, shared = int(p["top_k"]), int(p["shared_experts"])
    db, cb = int(p["dispatch_bytes_per_pair"]), int(p["combine_bytes_per_pair"])
    wg, sb = c["workgroups"], c["sector_bytes"]
    ranks = np.arange(n)
    own = np.diag(pairs).copy()
    sent = pairs.sum(axis=1)
    received = pairs.sum(axis=0)

    # 1. dispatch, 2. its barrier
    _, sectors, cyc = phase_cycles(tokens * top_k * db, c)
    end = pod.work(ranks, cyc)
    account(pod, ranks, reads=sectors, out=_one_each((sent - own) * db, wg),
            local=_one_each(own * db, wg))
    barrier(pod, ranks, fanout(pod, legs, end, pairs * db))

    # 3. experts
    ffn = 3 * hidden * int(p["expert_width"])
    slots_on = np.bincount(slot_rank, minlength=n)
    macs = Fraction(c["macs_per_cycle_per_cu"]) * c["n_cus"]
    hbm = Fraction(c["sectors_per_cycle_per_cu"]) * c["n_cus"]
    cycles = np.zeros(n, np.int64)
    sectors = np.zeros(n, np.int64)
    for r in range(n):
        weights = (int(slots_on[r]) + shared) * ffn
        compute = math.ceil((int(received[r]) + tokens * shared) * ffn / macs)
        stream = math.ceil(-(-weights // sb) / hbm)
        cycles[r] = max(1, compute, stream)
        sectors[r] = phase_cycles(weights + int(received[r]) * db, c)[1]
    pod.work(ranks, cycles)
    account(pod, ranks, reads=sectors, local=_one_each(
        (received + tokens * shared) * 2 * hidden, wg))

    # 4. combine, 5. its barrier
    share = [phase_cycles(int(received[r]) * cb, c) for r in range(n)]
    end = pod.work(ranks, np.array([s[2] for s in share], np.int64))
    account(pod, ranks, reads=np.array([s[1] for s in share], np.int64),
            out=_one_each((received - own) * cb, wg),
            local=_one_each(own * cb, wg))
    barrier(pod, ranks, fanout(pod, legs, end, pairs.T * cb))

    # 6. reduce
    _, sectors, cyc = phase_cycles(tokens * top_k * cb, c)
    pod.work(ranks, cyc)
    account(pod, ranks, reads=sectors,
            local=_one_each(np.full(n, tokens * 2 * hidden), wg))
    return pod.answer()
