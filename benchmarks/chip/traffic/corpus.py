"""Text traffic: a corpus drawn from the semantic-lattice generator.

A copy of the generator of ``repro.data.corpus.SemanticCorpusModel``
(``create`` + ``generate``), so that the benchmark's inputs do not
change when the program's copy does. Words have Zipfian frequencies
independent of topic; each sentence picks a topic and draws its words
from ``zipf(w) · exp(β · z_w · center[t])``.
"""

from __future__ import annotations

import numpy as np


def semantic_corpus(seed: int, vocab_size: int, sentences: int,
                    mean_sentence_len: int = 20, num_topics: int = 16,
                    num_features: int = 4, latent_dim: int = 12,
                    zipf_a: float = 1.05, beta: float = 4.0
                    ) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens int32 (T,), offsets int64 (S+1,))`` drawn from ``seed``."""
    rng = np.random.default_rng((seed, 0))
    centers = rng.normal(size=(num_topics, latent_dim))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    offs = 0.35 * rng.normal(size=(num_features, latent_dim))
    topics = rng.integers(0, num_topics, size=vocab_size)
    feats = (rng.random((vocab_size, num_features)) < 0.5).astype(np.int8)
    latents = centers[topics] + feats @ offs
    zipf = np.arange(1, vocab_size + 1, dtype=np.float64) ** (-zipf_a)
    zipf = zipf[rng.permutation(vocab_size)]
    zipf /= zipf.sum()

    logits = beta * (latents @ centers.T)                    # (V, K)
    logits -= logits.max(axis=0, keepdims=True)
    p = zipf[:, None] * np.exp(logits)
    p /= p.sum(axis=0, keepdims=True)
    cdfs = np.cumsum(p.T, axis=1)                            # (K, V)
    cdfs[:, -1] = 1.0

    rng = np.random.default_rng((seed, 1))
    lengths = np.clip(rng.poisson(mean_sentence_len, size=sentences), 3,
                      None).astype(np.int64)
    sent_topics = rng.integers(0, num_topics, size=sentences)
    offsets = np.zeros(sentences + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    u = rng.random(int(offsets[-1]))
    tokens = np.empty(len(u), dtype=np.int32)
    tok_topic = np.repeat(sent_topics, lengths)
    for k in range(num_topics):
        m = tok_topic == k
        tokens[m] = np.searchsorted(cdfs[k], u[m]).astype(np.int32)
    np.clip(tokens, 0, vocab_size - 1, out=tokens)
    return tokens, offsets
