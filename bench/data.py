"""Token batches for a cell, drawn from ``--seed``.

An order-2 Markov chain over the configuration's vocabulary (a copy of the
program's ``MarkovLM`` sampling rule): each next token is one of
``branching`` hashed successors of the last two tokens, or with
probability ``noise`` a uniform token.  Every batch of every seed has the
same shape; the seed changes only the tokens.
"""

from __future__ import annotations

import numpy as np

P31 = 2**31 - 1


def sample(seed: int, index: int, rows: int, seq: int, vocab: int, *,
           branching: int = 4, noise: float = 0.05) -> np.ndarray:
    """``(rows, seq + 1)`` int32 tokens: batch ``index`` of ``seed``."""
    mix = np.random.default_rng([seed, 0x7A11]).integers(1, P31, size=3)
    rng = np.random.default_rng([seed, index])
    out = np.empty((rows, seq + 1), dtype=np.int64)
    c1 = rng.integers(0, vocab, size=rows)
    c2 = rng.integers(0, vocab, size=rows)
    out[:, 0], out[:, 1] = c1, c2
    choice = rng.integers(0, branching, size=(rows, seq - 1))
    flip = rng.random((rows, seq - 1)) < noise
    uniform = rng.integers(0, vocab, size=(rows, seq - 1))
    a, b, c = (int(x) for x in mix)
    for t in range(seq - 1):
        base = (c1 * a + c2 * b) % P31
        nxt = (base + choice[:, t] * c) % vocab
        nxt = np.where(flip[:, t], uniform[:, t], nxt)
        out[:, t + 2] = nxt
        c1, c2 = c2, nxt
    return out.astype(np.int32)


def ring(seed: int, size: int, rows: int, seq: int, vocab: int, **kw):
    """``size`` distinct batches as ``(tokens, labels)``, each ``(rows, seq)``."""
    out = []
    for i in range(size):
        toks = sample(seed, i, rows, seq, vocab, **kw)
        out.append((toks[:, :-1].copy(), toks[:, 1:].copy()))
    return out
