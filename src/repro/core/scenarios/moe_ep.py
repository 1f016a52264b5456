"""Expert-parallel MoE decode layer under skewed routing as an Eidola scenario.

One MoE layer of a wide expert-parallel (EP) decode step: every rank holds
``tokens_per_device`` tokens, routes each to ``top_k`` experts, dispatches
every (token, expert) pair to the rank holding that expert, runs its
experts, and returns each pair's output to the token's rank (combine).
Unlike ``all_to_all``, the pairs a rank sends to each peer follow a real
router over a skewed expert popularity, so payloads differ per (source,
destination) and so do the ranks' expert times.

**Routing** (one draw per ``layer``; the reference is written from this):

* ``rng = np.random.default_rng(np.random.SeedSequence(layer))``.
* ``pi = rng.permutation(experts)``; expert ``pi[i]`` gets weight
  ``w = (i + 1) ** -zipf_s`` (float64).
* ``G = rng.gumbel(size=(devices * tokens_per_device, experts))``, float64,
  rank 0's tokens first, then rank 1's, and so on.
* Score ``s = 1 / (1 + exp(-(log(w) + G)))``, the sigmoid.
* The group-limited gate: the experts form ``expert_groups`` contiguous
  groups of ``experts / expert_groups``; a group's score is its largest
  score plus its second largest.  The ``topk_groups`` best groups are
  kept, then the ``top_k`` best experts inside them.  Both choices are a
  stable sort of the negated scores, so ties go to the lower index.
* Placement: ``experts + redundant_experts`` slots, slot ``s`` on rank
  ``s * devices // slots``.  Slot ``e < experts`` holds expert ``e``; slot
  ``experts + i`` holds a replica of the ``i``-th heaviest expert
  (descending weight, a stable sort, ties to the lower index).  A pair for
  a replicated expert goes to the original from an even source rank and to
  the replica from an odd one.  At 144 ranks and 256 + 32 slots that is
  expert ``e`` on rank ``e // 2`` and two replicas on each of ranks
  128-143.
* ``pairs[src, dst]`` counts the pairs rank ``src`` sends rank ``dst``; a
  pair on its own rank crosses no fabric.

**Each rank's program** (``Db``/``Cb`` the dispatch and combine bytes of
one pair; a workgroup's share of ``x`` bytes is ``max(1, x // workgroups)``
streamed as whole sectors at its even share of the CUs' sector rate):

1. ``moe_dispatch``: streams its share of ``tokens_per_device * top_k *
   Db`` bytes; each workgroup writes its share of the fabric-bound bytes
   out (``xgmi_out``) and of the local pairs' bytes locally.  It then
   emits one message to every peer, ascending, carrying ``pairs * Db``
   bytes ahead of its slot-0 flag (zero pairs: the flag alone).
2. ``wait_flags``: every peer's slot-0 flag, ascending.
3. ``moe_experts``: ``max(compute, weights)`` cycles, where compute is the
   MACs of its received pairs and its own ``tokens_per_device *
   shared_experts`` tokens (``3 * hidden * expert_width`` each) at the
   config's MAC rate (``macs_per_cycle_per_cu * n_cus``), and weights is
   the HBM time of its slots' and the shared experts' FP8 weights (``3 *
   hidden * expert_width`` bytes each) at the config's sector rate, each
   rounded up.  Each workgroup streams its share of the weights and the
   received pairs' bytes and writes its share of the BF16 outputs
   (``2 * hidden`` bytes a received pair and a shared token) locally.
4. ``moe_combine``: streams its share of its received pairs' ``Cb`` bytes,
   writes its share of the fabric-bound ones out and of its own pairs'
   locally, and emits the reverse fan-out: to each peer the pairs that
   peer sent it, times ``Cb``, ahead of its slot-1 flag.
5. ``wait_flags``: every peer's slot-1 flag, ascending.
6. ``moe_reduce``: streams its share of its tokens' returned outputs
   (``tokens_per_device * top_k * Cb`` bytes) and writes its share of the
   weighted sums (``tokens_per_device * 2 * hidden`` bytes) locally.

The defaults are a small shape the cycle engine runs in well under a
second; the DeepSeek-V3 widths (256 + 32 experts, top-8 of 4 of 8 groups,
hidden 7168, expert width 2048) come from a deployment such as
``bench/configs/deepseek_v3_ep144.json``.  ``dispatch_bytes_per_pair``
defaults to FP8 activations plus one fp32 scale per 128 channels,
``combine_bytes_per_pair`` to BF16 activations.

Open loop (the default) details rank 0 only; each peer's slot-0 flag lands
after the dispatch phase and its pairs' bytes over one ICI link, and its
slot-1 flag after the last dispatch flag, its own expert and combine
phases and its combine bytes over one link.
"""

from __future__ import annotations

import math
from fractions import Fraction
from typing import List, Optional, Tuple

import numpy as np

from ..config import SimConfig
from ..events import TraceBundle, register_phase
from ..memory import AddressMap
from ..scenario import (
    AffineRun,
    EmitRun,
    LoopPhase,
    Scenario,
    SymbolicProgram,
    TrafficOp,
    WGProgram,
    affine_of,
    local_writes,
    reads,
    register_scenario,
    xgmi_out,
)
from ..spans import count, span
from ..topology import HardwareSpec, V5E

__all__ = ["MoEExpertParallelScenario", "route_pairs"]

register_phase("moe_dispatch", color="green", glyph="d")
register_phase("moe_experts", color="blue", glyph="x")
register_phase("moe_combine", color="brown", glyph="c")
register_phase("moe_reduce", color="brown", glyph="s")

#: tokens whose router scores are drawn and gated at a time (memory bound)
_ROUTE_CHUNK = 16384


def _best(score: np.ndarray, k: int) -> np.ndarray:
    """Column indexes, ascending, of each row's ``k`` highest scores; of
    equal scores the lower index is taken first (as a stable sort of the
    negated scores would)."""
    if k >= score.shape[1]:
        return np.broadcast_to(np.arange(score.shape[1]), score.shape)
    kth = np.partition(score, score.shape[1] - k, axis=1)[:, -k][:, None]
    above = score > kth
    ties = score == kth
    room = k - above.sum(axis=1, keepdims=True)
    take = above | (ties & (np.cumsum(ties, axis=1) <= room))
    return np.nonzero(take)[1].reshape(-1, k)


def route_pairs(
    devices: int,
    tokens_per_device: int,
    *,
    experts: int,
    redundant_experts: int,
    top_k: int,
    expert_groups: int,
    topk_groups: int,
    zipf_s: float,
    layer: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """``(pairs, slot_rank)``: int64[devices, devices] pair counts and the
    rank of each expert slot, drawn as the module docstring says."""
    rng = np.random.default_rng(np.random.SeedSequence(int(layer)))
    perm = rng.permutation(experts)
    w = np.empty(experts)
    w[perm] = np.arange(1, experts + 1, dtype=np.float64) ** -float(zipf_s)
    logw = np.log(w)
    slots = experts + redundant_experts
    slot_rank = np.arange(slots, dtype=np.int64) * devices // slots
    home = slot_rank[:experts]
    alt = home.copy()
    heavy = np.argsort(-w, kind="stable")[:redundant_experts]
    alt[heavy] = slot_rank[experts:]
    per = experts // expert_groups
    n_tok = devices * tokens_per_device
    flat = np.zeros(devices * devices, np.int64)
    for t0 in range(0, n_tok, _ROUTE_CHUNK):
        c = min(_ROUTE_CHUNK, n_tok - t0)
        s = rng.gumbel(size=(c, experts))
        # s = 1 / (1 + exp(-(log w + G))), in place
        s += logw
        np.negative(s, out=s)
        np.exp(s, out=s)
        s += 1.0
        np.divide(1.0, s, out=s)
        # a group's score: its two best (their order does not change a sum)
        by_group = s.reshape(c, expert_groups, per)
        two = np.partition(by_group, per - 2, axis=2)[:, :, per - 2:]
        gscore = two[:, :, 0] + two[:, :, 1]
        keep = _best(gscore, topk_groups)
        cand = (keep[:, :, None] * per + np.arange(per)).reshape(c, -1)
        chosen = np.take_along_axis(
            cand, _best(np.take_along_axis(s, cand, axis=1), top_k), axis=1)
        src = (t0 + np.arange(c, dtype=np.int64)) // tokens_per_device
        dst = np.where((src % 2 == 0)[:, None], home[chosen], alt[chosen])
        flat += np.bincount(
            (src[:, None] * devices + dst).ravel(), minlength=devices * devices
        )
    return flat.reshape(devices, devices), slot_rank


@register_scenario
class MoEExpertParallelScenario(Scenario):
    """One MoE layer's dispatch, expert and combine over an EP pool, with
    per-pair payloads and per-rank expert time from a skewed router."""

    name = "moe_ep"
    closed_loop_capable = True

    def __init__(
        self,
        cfg: SimConfig,
        amap: Optional[AddressMap] = None,
        *,
        experts: int = 16,
        redundant_experts: int = 4,
        top_k: int = 4,
        expert_groups: int = 4,
        topk_groups: int = 2,
        hidden: int = 256,
        expert_width: int = 64,
        shared_experts: int = 1,
        dispatch_bytes_per_pair: Optional[int] = None,
        combine_bytes_per_pair: Optional[int] = None,
        tokens_per_device: int = 4,
        zipf_s: float = 0.3,
        layer: int = 3,
        writes_per_peer: int = 4,
        closed_loop: bool = False,
        devices_per_node: Optional[int] = None,
        fabric=None,
        link_bw=None,
        hw: HardwareSpec = V5E,
    ):
        super().__init__(cfg, amap)
        positive = dict(experts=experts, top_k=top_k,
                        expert_groups=expert_groups, topk_groups=topk_groups,
                        hidden=hidden, expert_width=expert_width,
                        tokens_per_device=tokens_per_device)
        for key, v in positive.items():
            if int(v) <= 0:
                raise ValueError(f"{key} must be positive")
        if experts % expert_groups:
            raise ValueError("expert_groups must divide experts")
        if topk_groups > expert_groups:
            raise ValueError("topk_groups must not exceed expert_groups")
        if top_k > topk_groups * (experts // expert_groups):
            raise ValueError("top_k exceeds the experts of the kept groups")
        if not 0 <= redundant_experts <= experts:
            raise ValueError("redundant_experts must lie in [0, experts]")
        if shared_experts < 0 or writes_per_peer < 0 or zipf_s < 0:
            raise ValueError(
                "shared_experts, writes_per_peer and zipf_s must be >= 0"
            )
        self.experts = int(experts)
        self.redundant_experts = int(redundant_experts)
        self.top_k = int(top_k)
        self.expert_groups = int(expert_groups)
        self.topk_groups = int(topk_groups)
        self.hidden = int(hidden)
        self.expert_width = int(expert_width)
        self.shared_experts = int(shared_experts)
        self.dispatch_bytes = int(
            dispatch_bytes_per_pair if dispatch_bytes_per_pair is not None
            else hidden + 4 * -(-hidden // 128)
        )
        self.combine_bytes = int(
            combine_bytes_per_pair if combine_bytes_per_pair is not None
            else 2 * hidden
        )
        self.tokens_per_device = int(tokens_per_device)
        self.zipf_s = float(zipf_s)
        self.layer = int(layer)
        self.writes_per_peer = int(writes_per_peer)
        self.closed_loop = bool(closed_loop)
        self.devices_per_node = devices_per_node
        self.hw = hw
        self._setup_fabric(
            devices_per_node=devices_per_node, hw=hw, fabric=fabric,
            link_bw=link_bw,
        )
        # slot 0: dispatch completion, slot 1: combine completion, each
        # rank writing its own column
        self.amap.claim_flag_block("moe_dispatch_barrier", 0, 1)
        self.amap.claim_flag_block("moe_combine_barrier", 1, 2)
        n = cfg.n_devices
        with span("moe.route"):
            self.pairs, slot_rank = route_pairs(
                n, self.tokens_per_device, experts=self.experts,
                redundant_experts=self.redundant_experts, top_k=self.top_k,
                expert_groups=self.expert_groups,
                topk_groups=self.topk_groups, zipf_s=self.zipf_s,
                layer=self.layer,
            )
            self.slots_on = np.bincount(slot_rank, minlength=n)
            self.received = self.pairs.sum(axis=0)
        off = self.pairs.copy()
        np.fill_diagonal(off, 0)
        count("moe.fabric_pairs", int(off.sum()))
        count("moe.hot_rank_pairs", int(self.received.max()))
        self._flag_aff = {
            slot: affine_of(lambda g, s=slot: self.amap.flag_addr(g, s), 0, n)
            for slot in (0, 1)
        }
        self.params = {
            "experts": self.experts,
            "redundant_experts": self.redundant_experts,
            "top_k": self.top_k,
            "tokens_per_device": self.tokens_per_device,
            "zipf_s": self.zipf_s,
            "layer": self.layer,
            "closed_loop": self.closed_loop,
            "devices_per_node": self.devices_per_node,
            "fabric": self.fabric_name,
        }

    @classmethod
    def default_amap(cls, cfg: SimConfig) -> AddressMap:
        return AddressMap(
            n_devices=cfg.n_devices, flag_slots=2
        ).with_partial_clearance()

    # ------------------------------------------------------------------

    def _stream(self, nbytes: int) -> Tuple[int, int, int]:
        """(bytes, sectors, cycles) of one workgroup's share of ``nbytes``."""
        cfg = self.cfg
        share = max(1, nbytes // cfg.workgroups)
        sectors = -(-share // cfg.sector_bytes)
        rate = (Fraction(cfg.sectors_per_cycle_per_cu) * cfg.n_cus
                / cfg.workgroups)
        return share, sectors, max(1, math.ceil(Fraction(sectors) / rate))

    def expert_cycles(self, rank: int) -> int:
        """Cycles of ``rank``'s expert phase: compute or weight streaming,
        whichever is longer."""
        cfg = self.cfg
        ffn = 3 * self.hidden * self.expert_width
        tokens = (int(self.received[rank])
                  + self.tokens_per_device * self.shared_experts)
        compute = math.ceil(Fraction(tokens * ffn) / (
            Fraction(cfg.macs_per_cycle_per_cu) * cfg.n_cus))
        weights = (int(self.slots_on[rank]) + self.shared_experts) * ffn
        sectors = -(-weights // cfg.sector_bytes)
        hbm = math.ceil(Fraction(sectors) / (
            Fraction(cfg.sectors_per_cycle_per_cu) * cfg.n_cus))
        return max(1, compute, hbm)

    def _out(self, fabric_bytes: int, local_bytes: int) -> List[TrafficOp]:
        wg = self.cfg.workgroups
        ops = []
        if fabric_bytes // wg:
            ops.append(xgmi_out(1, fabric_bytes // wg))
        if local_bytes // wg:
            ops.append(local_writes(1, local_bytes // wg))
        return ops

    def _fanout(self, rank: int, slot: int, payloads: np.ndarray) -> tuple:
        """EmitRuns to every peer below then above ``rank``, each member
        carrying its peer's payload ahead of this rank's ``slot`` flag."""
        n = self.cfg.n_devices
        return tuple(
            EmitRun(
                cnt, dst0=g0, slot0=slot,
                payload_bytes=tuple(int(p) for p in payloads[g0: g0 + cnt]),
                data_writes=self.writes_per_peer,
            )
            for g0, cnt in ((0, rank), (rank + 1, n - 1 - rank))
            if cnt
        )

    def _barrier(self, rank: int, slot: int) -> LoopPhase:
        n = self.cfg.n_devices
        aff = self._flag_aff[slot]
        return LoopPhase("wait_flags", wait_addrs=tuple(
            AffineRun(aff.at(g0), aff.step, cnt)
            for g0, cnt in ((0, rank), (rank + 1, n - 1 - rank))
            if cnt
        ))

    def _symbolic_phases(self, rank: int, *, emit: bool) -> SymbolicProgram:
        cfg = self.cfg
        sb = cfg.sector_bytes
        n_peers = cfg.n_egpus
        db, cb = self.dispatch_bytes, self.combine_bytes
        row = self.pairs[rank]
        col = self.pairs[:, rank]
        own = int(row[rank])
        received = int(self.received[rank])
        flags = [] if emit else [xgmi_out(n_peers, 8)]

        _, sectors, cycles = self._stream(
            self.tokens_per_device * self.top_k * db)
        dispatch = LoopPhase(
            "moe_dispatch", cycles,
            traffic=tuple([reads(sectors, sb),
                           *self._out((int(row.sum()) - own) * db, own * db),
                           *flags]),
            emits=self._fanout(rank, 0, row * db) if emit else (),
        )
        ffn = 3 * self.hidden * self.expert_width
        weights = (int(self.slots_on[rank]) + self.shared_experts) * ffn
        _, w_sectors, _ = self._stream(weights + received * db)
        outputs = (received + self.tokens_per_device * self.shared_experts) \
            * 2 * self.hidden
        experts = LoopPhase(
            "moe_experts", self.expert_cycles(rank),
            traffic=tuple([reads(w_sectors, sb),
                           *self._out(0, outputs)]),
        )
        _, c_sectors, c_cycles = self._stream(received * cb)
        combine = LoopPhase(
            "moe_combine", c_cycles,
            traffic=tuple([reads(c_sectors, sb),
                           *self._out((received - own) * cb, own * cb),
                           *flags]),
            emits=self._fanout(rank, 1, col * cb) if emit else (),
        )
        _, r_sectors, r_cycles = self._stream(
            self.tokens_per_device * self.top_k * cb)
        reduce = LoopPhase(
            "moe_reduce", r_cycles,
            traffic=tuple([reads(r_sectors, sb), *self._out(
                0, self.tokens_per_device * 2 * self.hidden)]),
        )
        return SymbolicProgram(
            (dispatch, self._barrier(rank, 0), experts, combine,
             self._barrier(rank, 1), reduce),
            group="all",
        )

    def _rank_programs(self, rank: int, *, emit: bool) -> List[WGProgram]:
        cfg = self.cfg
        shared = self._symbolic_phases(rank, emit=emit)
        return [
            WGProgram(
                wg=wg,
                cu=wg % cfg.n_cus,
                dispatch_cycle=(wg // cfg.n_cus) * cfg.dispatch_stagger_cycles,
                phases=shared,
            )
            for wg in range(cfg.workgroups)
        ]

    def programs(self) -> List[WGProgram]:
        return self._rank_programs(0, emit=False)

    def programs_for(self, device: int) -> List[WGProgram]:
        if not self.closed_loop:
            return super().programs_for(device)
        return self._rank_programs(device, emit=True)

    def traces(self) -> TraceBundle:
        cfg = self.cfg
        n = cfg.n_devices
        hw = self.hw
        bw = hw.ici_link_bw * hw.ici_links_per_axis / 1e9  # bytes per ns
        lat = hw.ici_hop_latency_s * 1e9
        start = cfg.cycles_to_ns(
            self._stream(self.tokens_per_device * self.top_k
                         * self.dispatch_bytes)[2])
        peers = range(1, n)
        t_d = {g: start + (int(self.pairs[g, 0]) * self.dispatch_bytes + 8)
               / bw + lat for g in peers}
        last = max(t_d.values())
        t_c = {
            g: last + cfg.cycles_to_ns(
                self.expert_cycles(g)
                + self._stream(int(self.received[g]) * self.combine_bytes)[2]
            ) + (int(self.pairs[0, g]) * self.combine_bytes + 8) / bw + lat
            for g in peers
        }
        bundle = TraceBundle(meta={"scenario": self.name, "n_devices": n,
                                   "layer": self.layer})
        lead = cfg.data_write_lead_ns
        wpp = self.writes_per_peer
        for slot, times in ((0, t_d), (1, t_c)):
            for g in peers:
                flag_t = times[g]
                if cfg.include_data_writes and wpp > 0:
                    t0 = max(0.0, flag_t - lead)
                    for i in range(wpp):
                        bundle.add(
                            wakeup_ns=t0 + (flag_t - t0) * (i + 1) / (wpp + 1),
                            addr=self.amap.partial_base
                            + ((slot * n + g) * wpp + i) * 64,
                            data=0xE0 + g % 16, size=8, src=g,
                        )
                bundle.add(wakeup_ns=flag_t,
                           addr=self.amap.flag_addr(g, slot), data=1, size=8,
                           src=g)
        return bundle
