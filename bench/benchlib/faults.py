"""Stand-ins for ``repro.core.simulate`` that the check has to refuse.

``control`` is the plain reference put in the program's place with every
time in ns computed in float32, the precision below the configuration's
float64 that a later change moving the pricing onto the chip would be
tempted to use.  The others break the program's own path underneath, one
fault each:

* ``altered``      an answer altered where it is produced (one counter + 1);
* ``stale``        the state returned unchanged: every call runs, and
                   answers with the first call's report;
* ``half``         half of the batch left out: half of the workgroups run;
* ``no_exchange``  the exchange between devices left out: the peers' data
                   writes are dropped and only their flags cross the fabric.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, Dict

import numpy as np

from .spec import load_reference


def control(scenario: str) -> Callable:
    ref = load_reference(scenario)

    def simulate(name, cfg, *, collect_segments=False, hw=None, **params):
        fields = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__}
        fields["sync"] = cfg.sync.value
        hardware = None if hw is None else {
            k: getattr(hw, k) for k in hw.__dataclass_fields__}
        got = ref.answer({"scenario": name, "sim_config": fields,
                          "hardware": hardware, "params": params},
                         real=np.float32)
        return SimpleNamespace(answer=got, wall_time_s=0.0, meta={})

    return simulate


def program_faults(simulate: Callable) -> Dict[str, Callable]:
    def altered(scenario, cfg, **kw):
        report = simulate(scenario, cfg, **kw)
        report.flag_reads += 1
        return report

    first = []

    def stale(scenario, cfg, **kw):
        report = simulate(scenario, cfg, **kw)
        if not first:
            first.append(report)
        return first[0]

    def half(scenario, cfg, **kw):
        return simulate(scenario, cfg.with_(workgroups=cfg.workgroups // 2),
                        **kw)

    def no_exchange(scenario, cfg, **kw):
        return simulate(scenario, cfg.with_(include_data_writes=False), **kw)

    return {"altered": altered, "stale": stale, "half": half,
            "no_exchange": no_exchange}
