"""Data substrate: corpus synthesis, vocabulary, skip-gram pairs, streams.

The paper trains on raw text (Wikipedia 14 GB / Web 268 GB). Offline we
substitute a synthetic corpus drawn from a generative model with *known*
semantic structure (`corpus.SemanticCorpusModel`) so that the evaluation
benchmarks (similarity / analogy / categorization) have exact gold data.
Everything downstream (vocab building, subsampling, window extraction,
negative-sampling tables, per-worker sample streams) is implemented in
full, as it would be for real text. Graphs (``graph``) enter as an
edge list and stream DeepWalk's random-walk pairs per worker.
"""

from repro.data.corpus import SemanticCorpusModel, Corpus
from repro.data.vocab import Vocab, build_vocab
from repro.data.pairs import (
    extract_pairs,
    AliasSampler,
    NegativeSampler,
    negative_sampler_fn,
    build_noise_table,
    stack_noise_tables,
    subsample_mask,
)
from repro.data.graph import (
    Graph,
    WalkStream,
    make_walk_streams,
    relabel_by_degree,
)
from repro.data.pipeline import (
    HostShardPlan,
    PairChunkStream,
    WorkerStream,
    make_worker_streams,
    prefetch_chunks,
    stacked_pair_batches,
)

__all__ = [
    "SemanticCorpusModel",
    "Corpus",
    "Vocab",
    "build_vocab",
    "extract_pairs",
    "AliasSampler",
    "NegativeSampler",
    "negative_sampler_fn",
    "build_noise_table",
    "stack_noise_tables",
    "subsample_mask",
    "HostShardPlan",
    "PairChunkStream",
    "WorkerStream",
    "make_worker_streams",
    "prefetch_chunks",
    "stacked_pair_batches",
    "Graph",
    "WalkStream",
    "make_walk_streams",
    "relabel_by_degree",
]
