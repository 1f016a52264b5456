"""Plain reference for the closed-loop ring all-reduce on a rail-optimized pod.

The textbook ring: ``n`` ranks in id order, each sending to ``rank + 1``
(mod ``n``).  The payload is cut into ``n`` chunks of ``payload // n``
bytes; each workgroup handles ``chunk // workgroups`` of them.  A rank

1. sends its own chunk (stream the share, write it out), then emits flag 0;
2. for each of the ``2(n - 1)`` ring steps ``s``: waits for flag ``s`` from
   its upstream rank, then works the step -- a reduce-scatter step (the
   first ``n - 1``) streams the incoming share and the local accumulator,
   an all-gather step streams the incoming share; every step writes the
   share locally, and every step but the last writes it on downstream and
   emits flag ``s + 1``.

Steps are walked one at a time, every rank together (see ``podlib`` for
the workgroup, wait, fabric and visibility model).  Imports nothing of
``repro``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from podlib import Pod, phase_cycles, pod_of


def answer(call: Dict, *, real=np.float64) -> Dict[Tuple, float]:
    c, hw, n, dpn, payload = pod_of(call)
    pod = Pod(c, hw, n, dpn, real=real,
              writes_per_step=int(call["params"]["writes_per_step"]))
    chunk = max(1, payload // n)
    share, sectors, cyc = phase_cycles(chunk, c)
    sb = c["sector_bytes"]
    ranks = np.arange(n)
    down = pod.route(ranks, (ranks + 1) % n)
    steps = 2 * (n - 1)

    end = pod.work(ranks, cyc, reads=sectors, sector_bytes=sb, out=1,
                   nbytes=share)
    visible = pod.emit(down, end, chunk)  # flag 0, indexed by the sender
    for s in range(steps):
        pod.wait(ranks, np.roll(visible, 1))  # rank r hears rank r - 1
        last = s == steps - 1
        end = pod.work(ranks, cyc, reads=sectors * (2 if s < n - 1 else 1),
                       sector_bytes=sb, local=1, out=0 if last else 1,
                       nbytes=share)
        if not last:
            visible = pod.emit(down, end, chunk)
    return pod.answer()
