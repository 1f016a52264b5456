"""Synthetic-but-learnable token pipeline.

Produces deterministic batches keyed by (step, host) — every host of a
multi-host job computes only its slice (``host_batch = global_batch /
n_hosts``), which is how a real cluster feeds a pjit'd train step.  Sequences
are drawn from a tiny induced Markov chain so models can actually reduce loss
(pure uniform noise has nothing to learn); document boundaries are packed with
separator tokens like a production LM pipeline.

``prefetch`` wraps any iterator with a background thread + bounded queue to
overlap host-side batch synthesis with device compute.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import numpy as np

__all__ = ["DataConfig", "SyntheticLMDataset", "prefetch"]


@dataclass(frozen=True)
class DataConfig:
    vocab: int = 1024
    seq_len: int = 128
    global_batch: int = 8
    n_hosts: int = 1
    seed: int = 0
    markov_order: int = 1
    separator_token: int = 0
    mean_doc_len: int = 64


class SyntheticLMDataset:
    """Deterministic Markov-chain LM data, shardable by host."""

    def __init__(self, cfg: DataConfig):
        if cfg.global_batch % cfg.n_hosts:
            raise ValueError("global_batch must divide evenly across hosts")
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed)
        # sparse transition structure => learnable bigram statistics: each
        # token has 16 random successors plus an escape to token 1, kept as
        # [vocab, 17] tables (a dense [vocab, vocab] matrix would be 550 GB
        # at a 262k vocabulary)
        V = cfg.vocab
        succ = rng.integers(0, V, size=(V, 16))
        logits = rng.normal(0.0, 2.0, size=(V, 16))
        self._succ = np.concatenate([succ, np.full((V, 1), 1 % V)], axis=1)
        logits = np.concatenate([logits, np.zeros((V, 1))], axis=1)
        p = np.exp(logits - logits.max(axis=1, keepdims=True))
        self._cumP = np.cumsum(p / p.sum(axis=1, keepdims=True), axis=1)

    @property
    def host_batch(self) -> int:
        return self.cfg.global_batch // self.cfg.n_hosts

    def batch(self, step: int, host: int = 0) -> Dict[str, np.ndarray]:
        """tokens/labels [host_batch, seq_len] for (step, host)."""
        cfg = self.cfg
        rng = np.random.default_rng(
            (cfg.seed * 1_000_003 + step) * 4099 + host
        )
        B, S = self.host_batch, cfg.seq_len
        toks = np.empty((B, S + 1), np.int64)
        state = rng.integers(0, cfg.vocab, size=B)
        doc_left = rng.geometric(1.0 / cfg.mean_doc_len, size=B)
        for t in range(S + 1):
            u = rng.random(B)
            pick = (self._cumP[state] > u[:, None]).argmax(axis=1)
            state = self._succ[state, pick]
            end = doc_left <= 0
            if end.any():
                state = np.where(end, cfg.separator_token, state)
                doc_left = np.where(
                    end, rng.geometric(1.0 / cfg.mean_doc_len, size=B), doc_left
                )
            toks[:, t] = state
            doc_left -= 1
        return {
            "tokens": toks[:, :S].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
        }

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


def prefetch(it: Iterator, depth: int = 2) -> Iterator:
    """Background-thread prefetch with a bounded queue."""
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    stop = object()

    def worker():
        try:
            for item in it:
                q.put(item)
        finally:
            q.put(stop)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        item = q.get()
        if item is stop:
            return
        yield item
