"""Built-in Eidola traffic scenarios.

Importing this package registers every built-in with the scenario registry
(:mod:`repro.core.scenario`):

* ``gemv_allreduce`` — the paper's fused GEMV+AllReduce kernel (Table 1),
  ported from the seed's hardwired workload model.
* ``ring_allreduce`` — chunked ring all-reduce; one wait/flag per ring step,
  arrival schedule synthesized from the collective cost model in
  :mod:`repro.core.topology`.
* ``all_to_all``     — MoE-dispatch-shaped incast: every peer pushes a token
  shard and a completion flag; the target barriers on all of them.
* ``pipeline_p2p``   — pipeline-parallel stage: per-microbatch activation
  wait -> forward compute -> p2p send to the next stage.
* ``hierarchical_allreduce`` — closed-loop cross-tier collective: intra-node
  ring reduce-scatter (ICI), leader ring all-reduce over the DCI uplinks,
  intra-node broadcast.
* ``moe_ep``         — one expert-parallel MoE layer under skewed routing:
  per-pair dispatch, per-rank expert time, the reverse combine.
"""

from .all_to_all import AllToAllScenario
from .gemv_allreduce import GemvAllReduceScenario
from .hierarchical_allreduce import HierarchicalAllReduceScenario
from .moe_ep import MoEExpertParallelScenario
from .pipeline_p2p import PipelineP2PScenario
from .ring_allreduce import RingAllReduceScenario

__all__ = [
    "AllToAllScenario",
    "GemvAllReduceScenario",
    "HierarchicalAllReduceScenario",
    "MoEExpertParallelScenario",
    "PipelineP2PScenario",
    "RingAllReduceScenario",
]
