"""Token rows of a training cell, from the seed, every row with the same
token-count profile.

Each row of ``seq`` tokens holds ``seq // mean_doc_len`` document ends
(``eos``) at positions drawn from the seed, and the rest is text whose
token counts follow a Zipf-Mandelbrot law, p(rank) ~ (rank + zipf_q) **
-zipf_s, over the ids from ``first_token`` up: the counts are rounded once
to the row's length, so every row of every seed repeats its commonest
token as often, its second as often, and so on. The seed decides which id
holds which rank (one ranking per stream, as a language keeps its common
words) and the order of each row. So the seed changes the tokens and not
the work: the embedding gradient sums as many repeats of each of its rows
on every seed.

The same ``(seed, step)`` gives the same block. Traffic keys under
``tokens``: ``mean_doc_len``, ``zipf_s``, ``zipf_q``, ``eos`` and
``first_token``.
"""
from __future__ import annotations

import numpy as np


def profile(n_ids: int, length: int, zipf_s: float, zipf_q: float
            ) -> np.ndarray:
    """Counts of the ranks 1..n_ids in ``length`` tokens of text, rounded
    by largest remainder so that they sum to ``length``."""
    p = (np.arange(1, n_ids + 1, dtype=np.float64) + zipf_q) ** -zipf_s
    want = p / p.sum() * length
    counts = np.floor(want).astype(np.int64)
    short = length - int(counts.sum())
    counts[np.argsort(counts - want, kind="stable")[:short]] += 1
    return counts


class ZipfDocs:
    def __init__(self, vocab: int, seq: int, batch: int, seed: int, *,
                 mean_doc_len: int, zipf_s: float, zipf_q: float, eos: int,
                 first_token: int):
        self.seq, self.batch = seq, batch
        self.seed, self.step, self.eos = int(seed), 0, eos
        self.n_eos = seq // mean_doc_len
        ranked = np.random.default_rng((self.seed, 0)).permutation(
            np.arange(first_token, vocab, dtype=np.int32))
        counts = profile(len(ranked), seq - self.n_eos, zipf_s, zipf_q)
        self.text = np.repeat(ranked, counts)

    def _row(self, rng: np.random.Generator) -> np.ndarray:
        row = np.empty(self.seq, dtype=np.int32)
        ends = np.zeros(self.seq, dtype=bool)
        ends[rng.choice(self.seq, self.n_eos, replace=False)] = True
        row[ends] = self.eos
        row[~ends] = rng.permutation(self.text)
        return row

    def next_batch(self) -> dict:
        rng = np.random.default_rng((self.seed, 1 + self.step))
        self.step += 1
        return {"tokens": np.stack([self._row(rng)
                                    for _ in range(self.batch)])}
