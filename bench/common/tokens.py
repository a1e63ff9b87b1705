"""Seeded token inputs.

Training rows are noisy repetitions of motifs drawn from a small bank,
as in the program's own synthetic data (``repro.data.synthetic``), so a
model can learn from them; every row of every step differs.  The batch
of a step is a pure function of (seed, step).  Serving prompts are
uniform over the vocabulary.
"""
from __future__ import annotations

import numpy as np


class TrainTokens:
    def __init__(self, *, vocab_size: int, batch: int, seq_len: int,
                 seed: int, motif_len: int = 16, n_motifs: int = 64,
                 noise: float = 0.05):
        self.vocab_size, self.batch, self.seq_len = vocab_size, batch, seq_len
        self.seed, self.noise = seed, noise
        bank = np.random.default_rng([seed, 0x5EED])
        self.motifs = bank.integers(0, vocab_size, (n_motifs, motif_len))

    def batch_at(self, step: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, step])
        pick = rng.integers(0, len(self.motifs), self.batch)
        reps = -(-self.seq_len // self.motifs.shape[1])
        toks = np.tile(self.motifs[pick], (1, reps))[:, :self.seq_len]
        flip = rng.random(toks.shape) < self.noise
        toks = np.where(flip, rng.integers(0, self.vocab_size, toks.shape),
                        toks)
        return toks.astype(np.int32)


def prompts(*, vocab_size: int, batch: int, prompt_len: int, seed: int,
            index: int, stream: int = 2) -> np.ndarray:
    rng = np.random.default_rng([seed, stream, index])
    return rng.integers(0, vocab_size, (batch, prompt_len)).astype(np.int32)
