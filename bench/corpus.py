"""Seeded document streams with planted near-duplicates.

A copy of the repository's synthetic corpus generator, kept with the
benchmark so that the traffic a cell measures cannot change under it. Each
configuration file gives the corpus numbers (duplicate share, length
distribution, edit intensity) of one public data set's shape.

Near-duplicates are made by token substitution and head/tail truncation of
a document emitted at most `window` documents earlier, so a stream mixes
fresh documents with edited copies at the configured rate.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["CorpusConfig", "SyntheticCorpus"]


@dataclasses.dataclass(frozen=True)
class CorpusConfig:
    name: str = "common_crawl"
    vocab: int = 50_000
    dup_rate: float = 0.40
    mean_len: int = 120             # tokens
    max_len: int = 256
    min_len: int = 24
    edit_rate_lo: float = 0.00      # near-duplicate edit intensity range
    edit_rate_hi: float = 0.08
    window: int = 4096              # how far back a duplicate can reference
    seed: int = 0


class SyntheticCorpus:
    """Streaming batch source. `next_batch(n)` -> (tokens, lengths, dup_of)."""

    def __init__(self, cfg: CorpusConfig):
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self._history: list[np.ndarray] = []   # ring of recent documents
        self._emitted = 0

    def _fresh_doc(self) -> np.ndarray:
        cfg = self.cfg
        ln = int(np.clip(self.rng.lognormal(np.log(cfg.mean_len), 0.5),
                         cfg.min_len, cfg.max_len))
        return self.rng.integers(0, cfg.vocab, ln).astype(np.uint32)

    def _edit(self, doc: np.ndarray) -> np.ndarray:
        cfg = self.cfg
        rate = self.rng.uniform(cfg.edit_rate_lo, cfg.edit_rate_hi)
        out = doc.copy()
        n_sub = self.rng.binomial(len(out), rate)
        if n_sub:
            pos = self.rng.choice(len(out), n_sub, replace=False)
            out[pos] = self.rng.integers(0, cfg.vocab, n_sub)
        # occasional head/tail truncation (a formatting change)
        if self.rng.random() < 0.2 and len(out) > cfg.min_len + 8:
            cut = self.rng.integers(1, 8)
            out = out[cut:] if self.rng.random() < 0.5 else out[:-cut]
        return out

    def next_batch(self, n: int):
        """(tokens (n, max_len) uint32, lengths (n,) int32, dup_of (n,))."""
        cfg = self.cfg
        docs, dup_of = [], []
        for _ in range(n):
            if self._history and self.rng.random() < cfg.dup_rate:
                lo = self._emitted - len(self._history)
                j = int(self.rng.integers(lo, self._emitted))
                docs.append(self._edit(self._history[j - lo]))
                dup_of.append(j)
            else:
                docs.append(self._fresh_doc())
                dup_of.append(-1)
            self._history.append(docs[-1])
            if len(self._history) > cfg.window:
                self._history.pop(0)
            self._emitted += 1
        tokens = np.zeros((n, cfg.max_len), np.uint32)
        lengths = np.zeros(n, np.int32)
        for i, d in enumerate(docs):
            tokens[i, :len(d)] = d
            lengths[i] = len(d)
        return tokens, lengths, np.asarray(dup_of, np.int64)
