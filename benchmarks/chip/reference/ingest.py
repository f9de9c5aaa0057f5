"""Plain reference of the ingestion path: which (center, context) ids
each worker's chunks hold.

Semantics (the paper's Divide phase and word2vec's pair extraction, as
this system defines them for a seed):

* RANDOM SAMPLING: worker ``w`` draws ``round(rate · N)`` sentence
  indices with replacement from ``default_rng((seed, 0x5EED, w))``;
  EQUAL PARTITIONING: worker ``w`` takes the ``w``-th of ``n``
  contiguous slices, cut at ``linspace(0, N, n + 1)``.
* Each worker's vocabulary keeps words with at least ``min_count``
  occurrences in its sample (``base_min_count / workers``), sorted by
  descending count (stable), capped at ``max_vocab``. The union of the
  workers' vocabularies, sorted the same way by summed counts, numbers
  the rows of every table.
* Pairs come block by block (``sentences_per_block`` sentences of the
  sample each), from ``default_rng(SeedSequence((0x91BE, 1, seed, w,
  epoch, block)))``: subsampling with keep probability
  ``(sqrt(f/t)+1)·t/f`` (or only out-of-vocabulary words dropped when
  ``t`` is None), a dynamic window drawn from ``[1, window]`` per
  token, pairs in both directions within a sentence, then one
  permutation of the block's pairs.
* A chunk is the next ``steps · batch`` pairs of the worker's block
  stream, reshaped to ``(steps, batch)``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

UNK = -1
_SAMPLE_TAG = 0x5EED
_PAIR_DOMAIN, _PAIR_BLOCK = 0x91BE, 1


def sample_indices(num_sentences: int, rate: float, worker: int,
                   seed: int, strategy: str = "random",
                   workers: int = 1) -> np.ndarray:
    if strategy == "equal":
        cut = np.linspace(0, num_sentences, workers + 1).astype(np.int64)
        return np.arange(cut[worker], cut[worker + 1], dtype=np.int64)
    if strategy != "random":
        raise ValueError(f"no reference for strategy {strategy!r}")
    rng = np.random.default_rng((seed, _SAMPLE_TAG, worker))
    target = max(1, int(round(rate * num_sentences)))
    return rng.integers(0, num_sentences, size=target, dtype=np.int64)


def select(tokens: np.ndarray, offsets: np.ndarray,
           idx: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The sentences ``idx`` (repeats allowed) as a flat corpus."""
    lengths = (offsets[1:] - offsets[:-1])[idx]
    new_offsets = np.zeros(len(idx) + 1, dtype=np.int64)
    np.cumsum(lengths, out=new_offsets[1:])
    starts = np.repeat(offsets[idx] - new_offsets[:-1], lengths)
    pos = np.arange(int(new_offsets[-1]), dtype=np.int64) + starts
    return tokens[pos], new_offsets


def _freq_order(counts: np.ndarray, min_count: int,
                max_size: int | None) -> np.ndarray:
    order = np.argsort(-counts, kind="stable")
    order = order[counts[order] >= max(min_count, 1)]
    return order if max_size is None else order[:max_size]


@dataclass
class WorkerData:
    sample: np.ndarray      # sentence indices
    lookup: np.ndarray      # (raw_vocab,) raw word → union row, or UNK
    counts: np.ndarray      # (V_union,) this worker's counts per union row


@dataclass
class Ingest:
    workers: list[WorkerData]
    union_size: int

    def noise(self, w: int, power: float = 0.75) -> np.ndarray:
        """Worker ``w``'s noise distribution over union rows: its counts
        raised to ``power``, normalised."""
        p = self.workers[w].counts.astype(np.float64) ** power
        return p / p.sum()


def build(tokens, offsets, raw_vocab: int, workers: int, rate: float,
          base_min_count: int, max_vocab: int | None, seed: int,
          strategy: str = "random") -> Ingest:
    """Every worker's sample and vocabulary, in union row numbering."""
    n_sent = len(offsets) - 1
    min_count = max(1, int(round(base_min_count / workers)))
    samples, vocabs = [], []
    for w in range(workers):
        idx = sample_indices(n_sent, rate, w, seed, strategy, workers)
        sub, _ = select(tokens, offsets, idx)
        counts = np.bincount(sub, minlength=raw_vocab).astype(np.int64)
        order = _freq_order(counts, min_count, max_vocab)
        samples.append(idx)
        vocabs.append((order, counts[order]))
    total = np.zeros(raw_vocab, dtype=np.int64)
    for order, c in vocabs:
        total[order] += c
    union = np.argsort(-total, kind="stable")
    union = union[total[union] > 0]
    union_row = np.full(raw_vocab, UNK, dtype=np.int64)
    union_row[union] = np.arange(len(union))
    out = []
    for idx, (order, c) in zip(samples, vocabs):
        lookup = np.full(raw_vocab, UNK, dtype=np.int32)
        lookup[order] = union_row[order]
        counts = np.zeros(len(union), dtype=np.int64)
        counts[union_row[order]] = c
        out.append(WorkerData(sample=idx, lookup=lookup, counts=counts))
    return Ingest(workers=out, union_size=len(union))


def block_pairs(tokens, offsets, wd: WorkerData, window: int,
                subsample_t: float | None, seed: int, worker: int,
                epoch: int, block: int) -> tuple[np.ndarray, np.ndarray]:
    """One block's pairs, in the order the stream yields them."""
    rng = np.random.default_rng(np.random.SeedSequence(
        (_PAIR_DOMAIN, _PAIR_BLOCK, seed, worker, epoch, block)))
    sub, offs = tokens, offsets
    toks = wd.lookup[sub]
    if subsample_t is not None:
        f = wd.counts / max(int(wd.counts.sum()), 1)
        fw = np.where(toks == UNK, 1.0, f[np.clip(toks, 0, None)])
        keep_p = np.minimum(1.0, (np.sqrt(fw / subsample_t) + 1.0)
                            * (subsample_t / np.maximum(fw, 1e-12)))
        keep = (rng.random(len(toks)) < keep_p) & (toks != UNK)
    else:
        keep = toks != UNK
    sent = np.repeat(np.arange(len(offs) - 1), np.diff(offs))
    toks, sent = toks[keep], sent[keep]
    n = len(toks)
    if n == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    dyn = rng.integers(1, window + 1, size=n)
    cs, xs = [], []
    for off in range(1, window + 1):
        i = np.arange(n - off)
        same = sent[i] == sent[i + off]
        fwd = i[same & (off <= dyn[i])]
        bwd = i[same & (off <= dyn[i + off])]
        cs += [toks[fwd], toks[bwd + off]]
        xs += [toks[fwd + off], toks[bwd]]
    c = np.concatenate(cs).astype(np.int32)
    x = np.concatenate(xs).astype(np.int32)
    perm = rng.permutation(len(c))
    return c[perm], x[perm]


def worker_chunks(tokens, offsets, ing: Ingest, w: int, *, chunks: int,
                  steps: int, batch: int, window: int,
                  subsample_t: float | None, sentences_per_block: int,
                  seed: int, epoch: int = 0
                  ) -> tuple[np.ndarray, np.ndarray]:
    """The first ``chunks`` chunks of worker ``w``: two ``(chunks,
    steps, batch)`` id arrays (the stream wraps to its start when the
    sample runs out)."""
    wd = ing.workers[w]
    need = chunks * steps * batch
    cs, xs, have, b = [], [], 0, 0
    n_blocks = -(-len(wd.sample) // sentences_per_block)
    while have < need:
        start = (b % n_blocks) * sentences_per_block
        sub, offs = select(tokens, offsets,
                           wd.sample[start:start + sentences_per_block])
        c, x = block_pairs(sub, offs, wd, window, subsample_t, seed, w,
                           epoch, b % n_blocks)
        cs.append(c)
        xs.append(x)
        have += len(c)
        b += 1
    shape = (chunks, steps, batch)
    return (np.concatenate(cs)[:need].reshape(shape),
            np.concatenate(xs)[:need].reshape(shape))
