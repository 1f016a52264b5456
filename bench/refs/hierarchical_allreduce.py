"""Plain reference for the closed-loop hierarchical all-reduce on a
rail-optimized pod.

Nodes of ``dpn`` ranks; local rank 0 leads its node.  Three stages:

1. **Reduce-scatter in the node**: the node's ranks form a ring (local
   ``l`` sends to ``l + 1`` mod ``dpn``) over chunks of ``payload // dpn``
   bytes.  A rank sends its chunk and emits flag 0, then for each of the
   ``dpn - 1`` steps ``k`` waits for flag ``k`` from its upstream and
   reduces (streams the incoming share and its accumulator, writes it
   locally); every step but the last forwards the share downstream and
   emits flag ``k + 1``.  Then every rank but the leader hands its reduced
   shard to the leader (one fabric write of its share, and a flag); the
   leader waits for those flags, local ranks in ascending order.
2. **Ring all-reduce of the leaders** (when there is more than one node):
   the leaders run the textbook ring (see ``ring_allreduce.py``) over
   chunks of ``payload // nodes`` bytes, ``2(nodes - 1)`` steps.
3. **Broadcast in the node**: the leader writes the whole payload to each
   of its ranks (one fabric write of its share per rank, and a flag to each
   rank, in ascending order); the others wait for the leader's flag.
   Every rank then reads the result (streams its share of the whole
   payload and writes it locally).

The same workgroup, wait, fabric and visibility model as the ring (see
``podlib``).  Imports nothing of ``repro``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from podlib import Pod, phase_cycles, pod_of


def answer(call: Dict, *, real=np.float64) -> Dict[Tuple, float]:
    c, hw, n, dpn, payload = pod_of(call)
    pod = Pod(c, hw, n, dpn, real=real,
              writes_per_step=int(call["params"]["writes_per_step"]))
    sb = c["sector_bytes"]
    nodes = n // dpn
    ranks = np.arange(n)
    node, local = np.divmod(ranks, dpn)
    leaders = ranks[local == 0]
    workers = ranks[local != 0]

    # 1. reduce-scatter around each node's ring, then the hand-off
    if dpn > 1:
        chunk = max(1, payload // dpn)
        share, sectors, cyc = phase_cycles(chunk, c)
        down = node * dpn + (local + 1) % dpn
        ring = pod.route(ranks, down)
        up = node * dpn + (local - 1) % dpn  # rank r hears rank up[r]
        end = pod.work(ranks, cyc, reads=sectors, sector_bytes=sb, out=1,
                       nbytes=share)
        visible = pod.emit(ring, end, chunk)
        for k in range(dpn - 1):
            pod.wait(ranks, visible[up])
            last = k == dpn - 2
            end = pod.work(ranks, cyc, reads=2 * sectors, sector_bytes=sb,
                           local=1, out=0 if last else 1, nbytes=share)
            if not last:
                visible = pod.emit(ring, end, chunk)
        end = pod.work(workers, cyc, out=1, nbytes=share)
        handoff = pod.emit(pod.route(workers, node[workers] * dpn), end, chunk)
        by_local = handoff.reshape(nodes, dpn - 1)  # workers in id order
        for l2 in range(dpn - 1):
            pod.wait(leaders, by_local[:, l2])

    # 2. the leaders' ring
    if nodes > 1:
        chunk = max(1, payload // nodes)
        share, sectors, cyc = phase_cycles(chunk, c)
        lring = pod.route(leaders, np.roll(leaders, -1))
        steps = 2 * (nodes - 1)
        end = pod.work(leaders, cyc, reads=sectors, sector_bytes=sb, out=1,
                       nbytes=share)
        visible = pod.emit(lring, end, chunk)
        for s in range(steps):
            pod.wait(leaders, np.roll(visible, 1))
            last = s == steps - 1
            end = pod.work(leaders, cyc,
                           reads=sectors * (2 if s < nodes - 1 else 1),
                           sector_bytes=sb, local=1, out=0 if last else 1,
                           nbytes=share)
            if not last:
                visible = pod.emit(lring, end, chunk)

    # 3. broadcast in the node, then every rank reads the result
    share, sectors, cyc = phase_cycles(payload, c)
    if dpn > 1:
        end = pod.work(leaders, cyc, out=dpn - 1, nbytes=share)
        for l2 in range(1, dpn):
            flag = pod.emit(pod.route(leaders, leaders + l2), end, payload)
            pod.wait(leaders + l2, flag)
    pod.work(ranks, cyc, reads=sectors, sector_bytes=sb, local=1,
             nbytes=share)
    return pod.answer()
