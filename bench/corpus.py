"""Each silo's private corpus, from the seed: token sequences packed to the
model's training length.

A silo's documents draw their tokens from a Zipf(``zipf``) law over the
vocabulary slice (id 0 is the document start), ranked in an order of the
silo's own, and with probability ``ngram_prob`` follow the previous token
by a successor map of the silo's own, so every silo has its own
vocabulary and bigram structure to learn.  Document lengths are
log-normal (median ``doc_len_median``, log-sd ``doc_len_sigma``); the
documents are packed back to back, without masks between them, into
sequences of ``seq_len + 1`` tokens (the inputs and the next token of
each).
"""

from __future__ import annotations

import numpy as np


def silo_corpus(seed: int, vocab: int, seq_len: int, n_seq: int,
                traffic: dict) -> np.ndarray:
    """(n_seq, seq_len + 1) int32 packed sequences of one silo."""
    rng = np.random.default_rng(seed)
    n = n_seq * (seq_len + 1)
    ranks = np.arange(1, vocab, dtype=np.float64)
    cdf = np.cumsum(ranks ** -traffic["zipf"])
    cdf /= cdf[-1]
    ids = 1 + rng.permutation(vocab - 1)           # rank -> token id
    succ = 1 + rng.integers(0, vocab - 1, size=vocab)
    tok = ids[np.minimum(np.searchsorted(cdf, rng.random(n)), vocab - 2)]
    follow = rng.random(n) < traffic["ngram_prob"]
    lengths = np.maximum(1, rng.lognormal(np.log(traffic["doc_len_median"]),
                                          traffic["doc_len_sigma"],
                                          size=n // 16 + 1).astype(np.int64))
    starts = np.cumsum(lengths)
    starts = np.concatenate([[0], starts[starts < n]])
    tok[starts] = 0
    follow[starts] = False
    follow[0] = False
    # a token that follows takes its predecessor's successor: resolve the
    # chains (geometric, rarely longer than a few dozen) to a fixed point
    for _ in range(256):
        nxt = np.where(follow, succ[np.roll(tok, 1)], tok)
        if np.array_equal(nxt, tok):
            break
        tok = nxt
    return tok.astype(np.int32).reshape(n_seq, seq_len + 1)
