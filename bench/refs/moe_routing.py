"""The router of the ``moe_ep`` reference, written from the scenario's
documented routing and not from its code.  Imports nothing of ``repro``.

Per layer: a random permutation gives expert popularity a Zipf law; each
token scores every expert by the sigmoid of its log-weight plus Gumbel
noise; DeepSeek-V3's group-limited gate keeps the best groups by the sum of
their two best scores, then the best experts inside them, ties to the lower
index; a pair goes to its expert's slot, or to the replica's slot when the
expert is one of the heaviest and the source rank is odd.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def placement(devices: int, experts: int, redundant: int,
              weight: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(home, alt, slot_rank)``: each expert's rank from an even and from
    an odd source rank, and the rank of every slot."""
    slots = experts + redundant
    slot_rank = np.array([s * devices // slots for s in range(slots)],
                         np.int64)
    home = slot_rank[:experts].copy()
    alt = home.copy()
    # heaviest first; equal weights keep the lower index first
    heavy = sorted(range(experts), key=lambda e: (-weight[e], e))[:redundant]
    for i, e in enumerate(heavy):
        alt[e] = slot_rank[experts + i]
    return home, alt, slot_rank


def top_set(score: np.ndarray, k: int) -> np.ndarray:
    """Per row, the columns of the ``k`` highest scores, where of equal
    scores the lower column comes first: every score above the ``k``-th
    highest, then as many of those equal to it as there is room for, from
    the left."""
    kth = -np.partition(-score, k - 1, axis=1)[:, k - 1:k]
    above = score > kth
    room = k - above.sum(axis=1, keepdims=True)
    equal = score == kth
    take = above | (equal & (np.cumsum(equal, axis=1) <= room))
    return np.nonzero(take)[1].reshape(-1, k)


def pairs_of(p: Dict, devices: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(pairs, slot_rank)`` of one call's parameters: ``pairs[src, dst]``
    counts the (token, expert) pairs rank ``src`` sends rank ``dst``."""
    experts = int(p["experts"])
    groups = int(p["expert_groups"])
    tokens = int(p["tokens_per_device"])
    rng = np.random.default_rng(np.random.SeedSequence(int(p["layer"])))
    perm = rng.permutation(experts)
    weight = np.zeros(experts)
    for i, e in enumerate(perm):
        weight[e] = float(i + 1) ** -float(p["zipf_s"])
    noise = rng.gumbel(size=(devices * tokens, experts))
    score = 1.0 / (1.0 + np.exp(-(np.log(weight) + noise)))

    per = experts // groups
    by_group = score.reshape(-1, groups, per)
    two = -np.partition(-by_group, 1, axis=2)[:, :, :2]  # best, second best
    group_score = two[:, :, 0] + two[:, :, 1]
    # lexsort orders by its last key first: score descending, index ascending
    gidx = np.broadcast_to(np.arange(groups), group_score.shape)
    kept = np.lexsort((gidx, -group_score), axis=1)[:, :int(p["topk_groups"])]
    # the kept groups' experts, in index order
    cand = (np.sort(kept, axis=1)[:, :, None] * per
            + np.arange(per)).reshape(len(score), -1)
    chosen = top_set(np.take_along_axis(score, cand, axis=1), int(p["top_k"]))
    chosen = np.take_along_axis(cand, chosen, axis=1)

    home, alt, slot_rank = placement(devices, experts,
                                     int(p["redundant_experts"]), weight)
    src = np.repeat(np.arange(devices), tokens)[:, None]
    dst = np.where(src % 2 == 0, home[chosen], alt[chosen])
    pairs = np.zeros((devices, devices), np.int64)
    np.add.at(pairs, (np.broadcast_to(src, dst.shape), dst), 1)
    return pairs, slot_rank
