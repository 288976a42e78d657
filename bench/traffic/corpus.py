"""Token batches for training traffic, made from the run's seed.

A copy of the program's synthetic corpus (``data/pipeline.SyntheticCorpus``),
kept here so that the inputs cannot change under the program: a skewed
unigram draw mixed with a fixed permutation bigram, so the stream has
structure to learn.  Every batch of one seed is distinct, and every seed
gives batches of the same shape.
"""
from __future__ import annotations

import numpy as np


def batches(seed: int, n: int, *, vocab: int, seq: int, batch: int,
            zipf_a: float, bigram_weight: float):
    """``n`` distinct batches ``{"tokens", "targets", "mask"}`` of shape
    ``(batch, seq)``, the same for the same arguments."""
    rng = np.random.default_rng([seed, vocab, seq, batch])
    unigram = np.arange(1, vocab + 1, dtype=np.float64) ** -zipf_a
    unigram /= unigram.sum()
    perm = rng.permutation(vocab)
    out = []
    for _ in range(n):
        toks = np.empty((batch, seq + 1), np.int32)
        toks[:, 0] = rng.choice(vocab, size=batch, p=unigram)
        iid = rng.choice(vocab, size=(batch, seq), p=unigram)
        follow = rng.random((batch, seq)) < bigram_weight
        for t in range(seq):
            toks[:, t + 1] = np.where(follow[:, t], perm[toks[:, t]], iid[:, t])
        out.append({"tokens": toks[:, :-1], "targets": toks[:, 1:],
                    "mask": np.ones((batch, seq), np.float32)})
    return out


def seed_key(seed: int):
    """A JAX PRNG key from the run's seed, all of its bits: ``PRNGKey``
    alone keeps only the low 32."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
