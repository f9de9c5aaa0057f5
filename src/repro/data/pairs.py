"""Skip-gram (center, context) pair extraction + negative sampling.

Faithful to word2vec/the paper:

* dynamic window — the effective window for each center is drawn
  uniformly from [1, win] (word2vec's ``b`` trick);
* frequent-word subsampling with the usual ``(sqrt(f/t)+1)·t/f`` keep
  probability;
* negative samples drawn from the unigram distribution raised to 3/4.

Pair extraction is vectorized numpy (host-side input pipeline); negative
sampling is a jittable inverse-CDF lookup so it can run on-device inside
the training step.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.data.corpus import Corpus
from repro.data.vocab import Vocab, UNK


def subsample_mask(
    tokens: np.ndarray, vocab: Vocab, t: float = 1e-4, rng: np.random.Generator | None = None
) -> np.ndarray:
    """word2vec frequent-word subsampling. tokens are vocab ids (UNK allowed)."""
    rng = rng or np.random.default_rng(0)
    freqs = vocab.unigram_probs()
    f = np.where(tokens == UNK, 1.0, freqs[np.clip(tokens, 0, None)])
    keep_prob = np.minimum(1.0, (np.sqrt(f / t) + 1.0) * (t / np.maximum(f, 1e-12)))
    keep = rng.random(len(tokens)) < keep_prob
    return keep & (tokens != UNK)


def window_pairs(
    toks: np.ndarray, sent_id: np.ndarray, window: int,
    rng: np.random.Generator,
) -> tuple[np.ndarray, np.ndarray]:
    """word2vec's pair cut of a token stream already filtered: a window
    drawn per token from ``[1, window]``, pairs in both directions
    within a sentence (``sent_id`` per token), then one permutation of
    all the pairs. Draws the windows, then the permutation, from
    ``rng``."""
    n = len(toks)
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)

    dyn = rng.integers(1, window + 1, size=n)
    centers_parts, contexts_parts = [], []
    for off in range(1, window + 1):
        # pair (i, i+off) valid both directions when off <= dyn of the center
        valid = np.arange(n - off)
        same_sent = sent_id[valid] == sent_id[valid + off]
        fwd = same_sent & (off <= dyn[valid])
        bwd = same_sent & (off <= dyn[valid + off])
        i = valid[fwd]
        centers_parts.append(toks[i])
        contexts_parts.append(toks[i + off])
        j = valid[bwd]
        centers_parts.append(toks[j + off])
        contexts_parts.append(toks[j])
    centers = np.concatenate(centers_parts).astype(np.int32)
    contexts = np.concatenate(contexts_parts).astype(np.int32)
    perm = rng.permutation(len(centers))
    return centers[perm], contexts[perm]


def extract_pairs(
    corpus: Corpus,
    vocab: Vocab,
    window: int = 10,
    subsample_t: float | None = 1e-4,
    seed: int | np.random.SeedSequence = 0,
    max_pairs: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Return (centers, contexts) vocab-id arrays for the whole corpus.

    Implements word2vec semantics: subsampled/UNK tokens are removed from
    the stream *before* windowing (so windows reach across removed
    words), and each center uses a dynamic window size.
    """
    rng = np.random.default_rng(seed)
    toks = vocab.encode(corpus.tokens)
    if subsample_t is not None:
        keep = subsample_mask(toks, vocab, t=subsample_t, rng=rng)
    else:
        keep = toks != UNK

    # Sentence id per token, so windows never cross sentence boundaries.
    sent_id = np.repeat(
        np.arange(corpus.num_sentences, dtype=np.int64),
        np.diff(corpus.offsets),
    )
    centers, contexts = window_pairs(toks[keep], sent_id[keep], window, rng)
    if max_pairs is not None:
        centers, contexts = centers[:max_pairs], contexts[:max_pairs]
    return centers, contexts


# ---------------------------------------------------------------------------
# Negative sampling: two interchangeable on-device draw primitives.
#
# ``cdf``   — inverse-CDF lookup, O(log V) searchsorted per draw. The
#             original path; kept as the distribution oracle.
# ``alias`` — Vose alias table, O(1) per draw: one randint + one uniform
#             + two gathers. The production path for large vocabularies.
#
# Both take the table as a traced argument so the same jitted epoch
# function serves every worker's own noise distribution.
# ---------------------------------------------------------------------------
def cdf_to_ids(cdf: jax.Array, u: jax.Array) -> jax.Array:
    """Inverse-CDF lookup: the id ``i`` with ``cdf[i-1] <= u < cdf[i]``.

    ``side='right'`` is load-bearing: it maps each ``u`` to the interval
    *above* it, so an id with zero probability (``cdf[i] == cdf[i-1]``,
    e.g. a union-vocab row this worker never saw) is unreachable.
    ``side='left'`` — the old behavior — returned such an id whenever
    ``u == 0.0`` with a leading zero-count row, or ``u`` landed exactly
    on a duplicated CDF boundary; at B·K draws per step those hits occur
    in practice and wrote to rows absent from the worker's vocabulary,
    corrupting the merge presence mask.
    """
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, cdf.shape[0] - 1).astype(jnp.int32)


def sample_negatives_cdf(
    cdf: jax.Array, key: jax.Array, shape: tuple[int, ...]
) -> jax.Array:
    u = jax.random.uniform(key, shape, dtype=jnp.float32)
    return cdf_to_ids(cdf, u)


def sample_negatives_alias(
    table: dict, key: jax.Array, shape: tuple[int, ...]
) -> jax.Array:
    prob, alias = table["prob"], table["alias"]
    k_idx, k_u = jax.random.split(key)
    idx = jax.random.randint(k_idx, shape, 0, prob.shape[0], dtype=jnp.int32)
    u = jax.random.uniform(k_u, shape, dtype=jnp.float32)
    return jnp.where(u < prob[idx], idx, alias[idx]).astype(jnp.int32)


NEGATIVE_SAMPLERS = {
    "cdf": sample_negatives_cdf,
    "alias": sample_negatives_alias,
}


def negative_sampler_fn(kind: str):
    """``fn(table, key, shape) -> (shape,) int32`` for ``kind``."""
    try:
        return NEGATIVE_SAMPLERS[kind]
    except KeyError:
        raise ValueError(
            f"unknown negative sampler {kind!r}; expected one of "
            f"{sorted(NEGATIVE_SAMPLERS)}") from None


def unigram_noise_probs(vocab_counts: np.ndarray, power: float = 0.75) -> np.ndarray:
    """word2vec noise distribution: unigram counts raised to 3/4."""
    p = np.asarray(vocab_counts, dtype=np.float64) ** power
    s = p.sum()
    return p / s if s > 0 else np.full_like(p, 1.0 / len(p))


# ---------------------------------------------------------------------------
# Noise-table layouts. An UpdateEngine declares which layout its draw
# consumes (`engine.table_kind`); these helpers build it — one table per
# sub-model, host-side, then stacked along a leading worker axis so the
# tables shard over the `worker` mesh axis like the parameter tables.
# ---------------------------------------------------------------------------
def build_noise_table(vocab_counts: np.ndarray, kind: str = "cdf",
                      power: float = 0.75):
    """One vocab's unigram^0.75 noise table in the layout ``kind``
    draws from: a ``(V,)`` float32 CDF, or a ``{'prob', 'alias'}`` Vose
    alias table (float32/int32 — VMEM-resident operands of the fused
    kernel)."""
    p = unigram_noise_probs(vocab_counts, power)
    if kind == "cdf":
        c = np.cumsum(p)
        c[-1] = 1.0
        return jnp.asarray(c, dtype=jnp.float32)
    if kind == "alias":
        from repro.core.distributions import build_alias_table

        prob, alias = build_alias_table(p)
        return {"prob": jnp.asarray(prob, dtype=jnp.float32),
                "alias": jnp.asarray(alias, dtype=jnp.int32)}
    raise ValueError(f"unknown noise-table kind {kind!r}; "
                     f"expected 'cdf' or 'alias'")


def stack_noise_tables(counts_per_worker: list[np.ndarray], kind: str = "cdf",
                       power: float = 0.75):
    """Stacked per-worker noise tables: ``(n, V)`` CDFs, or
    ``{'prob': (n, V), 'alias': (n, V)}`` alias tables. Each sub-model
    draws from its *own* sample's noise distribution, exactly as a
    standalone word2vec run on that sub-corpus would (paper §3.2)."""
    tables = [build_noise_table(c, kind=kind, power=power)
              for c in counts_per_worker]
    if kind == "cdf":
        return jnp.stack(tables)
    return {k: jnp.stack([t[k] for t in tables]) for k in ("prob", "alias")}


class NegativeSampler:
    """Unigram^0.75 sampler: inverse-CDF lookup, jittable and vectorized."""

    def __init__(self, vocab_counts: np.ndarray, power: float = 0.75):
        p = unigram_noise_probs(vocab_counts, power)
        cdf = np.cumsum(p)
        cdf[-1] = 1.0
        self.cdf = jnp.asarray(cdf, dtype=jnp.float32)
        self.probs = jnp.asarray(p, dtype=jnp.float32)

    def sample(self, key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
        return sample_negatives_cdf(self.cdf, key, shape)


class AliasSampler:
    """Unigram^0.75 sampler via Vose's alias method: O(V) build, O(1) draw."""

    def __init__(self, vocab_counts: np.ndarray, power: float = 0.75):
        from repro.core.distributions import build_alias_table

        p = unigram_noise_probs(vocab_counts, power)
        prob, alias = build_alias_table(p)
        self.prob = jnp.asarray(prob, dtype=jnp.float32)
        self.alias = jnp.asarray(alias, dtype=jnp.int32)
        self.probs = jnp.asarray(p, dtype=jnp.float32)

    @property
    def table(self) -> dict:
        return {"prob": self.prob, "alias": self.alias}

    def sample(self, key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
        return sample_negatives_alias(self.table, key, shape)
