"""The plain ingestion reference reproduces, id for id, the chunks the
program's pair stream yields for the same seed (both strategies the
cells use, with and without subsampling, past a wrap of the stream)."""

import numpy as np
import pytest

from benchmarks.chip.reference import ingest
from benchmarks.chip.traffic.corpus import semantic_corpus


@pytest.mark.parametrize("strategy,subsample_t", [
    ("random", 1e-4), ("random", None), ("equal", None)])
def test_reference_chunks_equal_the_program_stream(strategy, subsample_t):
    from repro.core.driver import prepare_training
    from repro.core.sgns import SGNSConfig
    from repro.data.corpus import Corpus

    seed, workers, raw = 2 ** 31 - 5, 4, 1500
    tokens, offsets = semantic_corpus(seed, raw, 800)
    setup = prepare_training(
        Corpus(tokens, offsets), raw, strategy, workers, SGNSConfig(0, dim=8),
        epochs=1, batch_size=32, rate=0.25, subsample_t=subsample_t,
        base_min_count=4, seed=seed, max_steps_per_epoch=4, engine="sparse",
        steps_per_chunk=4, sentences_per_block=16)
    stream = setup.plan.chunk_stream(setup.streams, batch_size=32,
                                     steps_per_chunk=4, sentences_per_block=16)
    it = stream.chunks(0, None)
    prog = [next(it) for _ in range(40)]         # past the end of a sample
    ing = ingest.build(tokens, offsets, raw, workers, 0.25, 4, 300_000, seed,
                       strategy)
    assert ing.union_size == setup.union_vocab.size
    for w in range(workers):
        c, x = ingest.worker_chunks(tokens, offsets, ing, w, chunks=40,
                                    steps=4, batch=32, window=10,
                                    subsample_t=subsample_t,
                                    sentences_per_block=16, seed=seed)
        assert np.array_equal(c, np.stack([p[0][w] for p in prog]))
        assert np.array_equal(x, np.stack([p[1][w] for p in prog]))


def test_padded_noise_table_is_the_programs_and_never_draws_a_pad_row():
    """The tables hold more rows than the union vocabulary: the noise
    table over the padded counts is the program's own Vose table, row
    for row, and gives the pad rows no weight."""
    from repro.core.distributions import alias_implied_probs
    from repro.data.pairs import build_noise_table

    from benchmarks.chip.kinds.train import padded
    from benchmarks.chip.reference import sgns

    rng = np.random.default_rng(2 ** 31 + 9)
    counts = rng.integers(1, 1000, size=300)
    rows = 384
    prog = build_noise_table(padded(counts, rows), kind="alias")
    q = counts ** 0.75
    prob, alias = sgns.alias_table(padded(q / q.sum(), rows))
    assert np.array_equal(np.asarray(prog["prob"]), prob)
    assert np.array_equal(np.asarray(prog["alias"]), alias)
    implied = alias_implied_probs(prob.astype(np.float64), alias)
    assert np.all(implied[300:] == 0.0)
    assert np.allclose(implied[:300], q / q.sum(), rtol=1e-5)
    with pytest.raises(ValueError):
        padded(counts, 299)
