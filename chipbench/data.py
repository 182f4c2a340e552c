"""Seeded token batches: the benchmark's own copy of ``repro.data.synthetic``.

``lm_batch`` is copied so that no change to the program can change the
yardstick's inputs. It is pure numpy and runs on the runtime's prefetch
thread. Every step draws new rows from ``(seed, step)``: a noisy periodic
copy process over a zipf unigram base, the same distribution the program's
own generator draws.
"""

from __future__ import annotations

import numpy as np

__all__ = ["lm_batch"]


def lm_batch(
    seed: int,
    step: int,
    *,
    vocab_size: int,
    batch: int,
    seq_len: int,
    period: int = 16,
    noise: float = 0.15,
) -> dict[str, np.ndarray]:
    """Tokens ``(batch, seq_len)`` int32 for one step of one seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    shape = (batch, seq_len)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks**-1.1
    base = rng.choice(vocab_size, size=(batch, period), p=p / p.sum())
    reps = -(-seq_len // period)
    tok = np.tile(base, (1, reps))[:, :seq_len]
    corrupt = rng.random(shape) < noise
    rand_tok = rng.integers(0, vocab_size, shape)
    return {"tokens": np.where(corrupt, rand_tok, tok).astype(np.int32)}
