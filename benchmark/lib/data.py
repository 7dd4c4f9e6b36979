"""The benchmark's traffic generator: seeded token batches.

One general generator reads a traffic file's ``data`` group (``order``,
``pool_steps``).  The stream is the repo's ``synthetic_lm`` task (with
probability ``order`` the next token is ``(5 * cur + 17) % vocab``, else
uniform), so a model can cut its loss below ``log(vocab)`` within a few
steps; it is copied here so that the yardstick does not change under a
later PR.
"""

from __future__ import annotations

import numpy as np


def affine_chain(rng, n: int, seq: int, vocab: int, order: float):
    """int32 [n, seq] token sequences."""
    toks = np.empty((n, seq), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=n)
    keep = rng.random((n, seq)) < order
    rand = rng.integers(0, vocab, size=(n, seq), dtype=np.int32)
    for t in range(1, seq):
        det = (5 * toks[:, t - 1] + 17) % vocab
        toks[:, t] = np.where(keep[:, t], det, rand[:, t])
    return toks


def batch_pool(spec: dict, seed: int, workers: int, batch: int, seq: int,
               vocab: int):
    """``pool[step][worker]`` = int32 [batch, seq], ``pool_steps`` fresh
    batches a worker, all drawn from ``seed``.  A run longer than the
    pool starts it again."""
    steps = int(spec["pool_steps"])
    rng = np.random.default_rng([int(seed), 0xDA7A])
    toks = affine_chain(rng, steps * workers * batch, seq, vocab,
                        float(spec["order"]))
    return toks.reshape(steps, workers, batch, seq)
