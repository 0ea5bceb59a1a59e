"""Seeded synthetic token stream: the benchmark's own generator.

A copy of the program's ``SyntheticLM`` rule (rows repeat a short pattern
drawn from a fixed bank, with a few noise tokens), kept here so that no
change to the program can change the traffic.  Batches are a pure function
of ``(seed, step)``; any seed up to 2**63 is accepted.  Shapes never
depend on the seed, so every seed carries the same work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

__all__ = ["SyntheticTokens"]

_MAXP = 8


@dataclasses.dataclass(frozen=True)
class SyntheticTokens:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int
    n_patterns: int = 512
    noise: float = 0.02

    def _rng(self, *words: int) -> np.random.Generator:
        key = [self.seed % 2**64, sum(w << (21 * i) for i, w in
                                      enumerate(words)) % 2**64]
        return np.random.Generator(np.random.Philox(key=key))

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        bank = self._rng(0, 0).integers(
            2, self.vocab_size, size=(self.n_patterns, _MAXP))
        rng = self._rng(1, step)
        n = self.global_batch
        pat = rng.integers(0, self.n_patterns, size=n)
        periods = 3 + (pat % (_MAXP - 3))
        offs = rng.integers(0, _MAXP, size=n)
        pos = np.arange(self.seq_len + 1)[None, :]
        toks = bank[pat[:, None], (pos + offs[:, None]) % periods[:, None]]
        if self.noise > 0:
            mask = rng.random(toks.shape) < self.noise
            toks = np.where(mask, rng.integers(2, self.vocab_size,
                                               size=toks.shape), toks)
        return {"tokens": toks[:, :-1].astype(np.int32),
                "targets": toks[:, 1:].astype(np.int32)}
