"""The one generator of ``simulate()`` calls: a configuration, a mix and a
seed.

A call is a plain dict, the same for the program and the reference::

    {"scenario": "ring_allreduce",
     "sim_config": {...every SimConfig field, "sync": "spin"},
     "hardware": {...HardwareSpec fields} or None,
     "params": {...the keyword arguments of simulate()}}

``params`` merges the configuration's ``deployment``, the mix's fixed
``params``, and what the mix varies from call to call:

* ``walk``: ``{name: [v0, v1, ...]}`` -- a recorded sequence, such as the
  gradient buckets of one training step.  The seed picks where the stream
  starts; calls then walk the sequence in order and wrap at its end.
* ``draws``: ``{name: {"dist": "uniform", "low": a, "high": b}}`` --
  continuous draws, stratified: calls come in blocks of ``strata``, and
  within a block each draw takes one quantile stratum of its range, in an
  order and at an offset inside the stratum both drawn from the seed.
  Every seed thus sees nearly the same spread of values in another order.
  A drawn call that repeats an earlier one is drawn again.
"""

from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np


def _uniform(spec: Dict, q: float) -> float:
    if spec["dist"] != "uniform":
        raise ValueError(f"unknown dist {spec['dist']!r}; known: uniform")
    lo, hi = float(spec["low"]), float(spec["high"])
    return lo + (hi - lo) * q


class CallStream:
    """Seeded, endless stream of one cell's ``simulate()`` calls."""

    def __init__(self, config: Dict, mix: Dict, seed: int):
        self.scenario = mix["scenario"]
        self.sim_config = {**config["sim_config"], "sync": mix["sync"]}
        self.hardware = config.get("hardware")
        self.base = {**config.get("deployment", {}), **mix.get("params", {})}
        self.walk = mix.get("walk", {})
        self.draws = mix.get("draws", {})
        self.strata = int(mix.get("strata", 16))
        # SeedSequence takes any non-negative int, however large
        self.rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
        lengths = {len(v) for v in self.walk.values()}
        if len(lengths) > 1:
            raise ValueError("the walked sequences differ in length")
        self.length = lengths.pop() if lengths else 1
        self.pos = int(self.rng.integers(self.length))
        self._block: List[Dict[str, int]] = []
        self._seen: set = set()

    def _new_block(self) -> List[Dict[str, int]]:
        perms = {name: self.rng.permutation(self.strata) for name in self.draws}
        return [{name: int(p[i]) for name, p in perms.items()}
                for i in range(self.strata)]

    def _drawn(self) -> Dict:
        if not self.draws:
            return {}
        if not self._block:
            self._block = self._new_block()
        strata = self._block.pop(0)
        for _ in range(1000):
            out = {name: _uniform(spec, (strata[name] + float(self.rng.random()))
                                  / self.strata)
                   for name, spec in self.draws.items()}
            key = tuple(sorted(out.items()))
            if key not in self._seen:
                self._seen.add(key)
                return out
        raise RuntimeError("the mix's draws cannot give another new call")

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        params = dict(self.base)
        for name, seq in self.walk.items():
            params[name] = seq[self.pos]
        self.pos = (self.pos + 1) % self.length
        params.update(self._drawn())
        return {"scenario": self.scenario, "sim_config": self.sim_config,
                "hardware": self.hardware, "params": params}
