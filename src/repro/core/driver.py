"""End-to-end paper pipeline: divide → async train → merge → evaluate.

This is the high-level API used by the examples, benchmarks and tests:

    result = run_pipeline(corpus, gen, strategy="shuffle", num_workers=10, ...)

Vocabulary policy (paper §4.2):

* ``shuffle`` — one global frequency-capped vocabulary, precomputed
  before epoch 0 and shared by all sub-models;
* ``random`` / ``equal`` — each sub-model builds its own vocabulary from
  its sample with ``min_count = base_min_count / num_workers``; merge
  happens over the union (ALiR's case 2).

All sub-models train in the *union* index space so tables stack into
``(n, V_union, d)``; each worker's pair stream only ever emits its own
vocabulary's ids, so absent rows are never touched.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.sgns import SGNSConfig
from repro.core.async_trainer import AsyncShardTrainer, make_sync_epoch
from repro.core.engine import get_engine
from repro.core.merge import StackedModels, merge as merge_models
from repro.core.schedule import plan_epoch
from repro.data.corpus import Corpus
from repro.data.graph import (Graph, degree_vocab, expected_walk_pairs,
                              make_walk_streams, relabel_by_degree)
from repro.data.pairs import build_noise_table, stack_noise_tables
from repro.data.vocab import Vocab, build_vocab, union_vocab, UNK
from repro.data.pipeline import (
    HostShardPlan, make_worker_streams, prefetch_chunks)


# ---------------------------------------------------------------------------
# PRNG streams. Every per-epoch key is a fold_in chain from a single
# root key: fold_in(fold_in(PRNGKey(seed), stream), epoch). The old
# arithmetic seeds (PRNGKey(seed*1000 + epoch), seed*77 + epoch,
# seed*31 + epoch) collide across distinct (seed, epoch) pairs — e.g.
# seed=1/epoch=1000 and seed=2/epoch=0 shared a stream — so two runs
# that should be independent sampled identical negatives/permutations.
# ---------------------------------------------------------------------------
_STREAM_ASYNC_DATA = 0      # per-chunk keys for the async workers' epochs
_STREAM_SYNC_EPOCH = 1      # the sync baseline's in-epoch negative draws
_STREAM_SYNC_PERM = 2       # the sync baseline's numpy pair permutation

# Leading entropy word of every numpy SeedSequence built here. Each
# module that seeds numpy generators owns a distinct domain constant in
# position 0, so its tuples can never alias another module's no matter
# what user seed/stream/epoch values follow (the pipeline's pair-
# extraction tuples, for instance, would otherwise collide with these
# whenever stream == a worker index).
_SEED_DOMAIN = 0xD21  # driver epoch streams


def _epoch_key(seed: int, stream: int, epoch: int) -> jax.Array:
    """Collision-free per-(seed, stream, epoch) PRNG key."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(seed), stream), epoch)


def worker_chunk_key(seed: int, epoch: int, chunk: int, num_workers: int,
                     worker: int) -> jax.Array:
    """The exact PRNG key worker ``worker`` consumes for chunk ``chunk``
    of ``epoch`` inside :func:`train_submodels`'s loop (epoch-stream key
    folded with the chunk index, then split over workers). The elastic
    runner replays this derivation so a worker resumed from a
    :class:`repro.elastic.WorkerCursor` — possibly on a different host —
    draws bit-identical negatives and step keys."""
    ep_key = _epoch_key(seed, _STREAM_ASYNC_DATA, epoch)
    return jax.random.split(
        jax.random.fold_in(ep_key, chunk), num_workers)[worker]


def _epoch_rng(seed: int, stream: int, epoch: int) -> np.random.Generator:
    """numpy counterpart of :func:`_epoch_key` (a domain-tagged
    SeedSequence: distinct (seed, stream, epoch) → distinct streams,
    disjoint from every other module's numpy streams)."""
    return np.random.default_rng(
        np.random.SeedSequence((_SEED_DOMAIN, seed, stream, epoch)))


@jax.jit
def _mean_loss(chunk_losses):
    """Scalar epoch loss from the list of per-chunk loss arrays. Jitted
    so it stays an SPMD computation on worker-sharded global arrays
    (multi-host); the result is replicated, hence float()-able on every
    host. Eager reductions would need fully-addressable shards."""
    return jnp.mean(jnp.concatenate(chunk_losses, axis=-1))


def _tiled_permutation(rng: np.random.Generator, n_pairs: int,
                       need: int) -> np.ndarray:
    """``need`` pair indices covering [0, n_pairs) as evenly as possible:
    whole independent permutations back to back. The old path tiled ONE
    permutation verbatim, so a corpus smaller than a batch replayed its
    pairs in identical order every pass within the epoch."""
    if n_pairs <= 0:
        raise ValueError("no training pairs extracted from the corpus")
    reps = -(-need // n_pairs)
    if reps == 1:
        return rng.permutation(n_pairs)[:need]
    return np.concatenate(
        [rng.permutation(n_pairs) for _ in range(reps)])[:need]


# ---------------------------------------------------------------------------
def _project_vocab(worker_vocab: Vocab, union: Vocab, raw_vocab_size: int) -> Vocab:
    """Worker vocabulary re-indexed into union-vocab id space."""
    lookup = np.full(raw_vocab_size, UNK, dtype=np.int32)
    union_ids = union.lookup[worker_vocab.word_ids]
    lookup[worker_vocab.word_ids] = union_ids
    counts = np.zeros(union.size, dtype=np.int64)
    counts[union_ids] = worker_vocab.counts
    return Vocab(word_ids=union.word_ids, counts=counts, lookup=lookup)


def build_worker_vocabs(
    corpus: Corpus,
    raw_vocab_size: int,
    strategy: str,
    num_workers: int,
    rate: float,
    max_vocab: int | None = 300_000,
    base_min_count: int = 100,
    seed: int = 0,
) -> tuple[list[Vocab], Vocab, np.ndarray]:
    """Returns (projected worker vocabs, union vocab, presence mask (n, V))."""
    if strategy == "shuffle":
        g = build_vocab(corpus, raw_vocab_size, min_count=1, max_size=max_vocab)
        union = g
        workers = [g] * num_workers
        mask = np.ones((num_workers, union.size), dtype=bool)
        return list(workers), union, mask

    from repro.core.sampling import sample_sentence_indices

    min_count = max(1, int(round(base_min_count / num_workers)))
    per_worker = []
    for w in range(num_workers):
        idx = sample_sentence_indices(
            corpus.num_sentences, strategy, rate, w, num_workers, epoch=0, seed=seed)
        sub = corpus.select(idx)
        per_worker.append(build_vocab(sub, raw_vocab_size, min_count=min_count,
                                      max_size=max_vocab))
    union = union_vocab(per_worker, raw_vocab_size)
    projected = [_project_vocab(v, union, raw_vocab_size) for v in per_worker]
    mask = np.zeros((num_workers, union.size), dtype=bool)
    for w, v in enumerate(per_worker):
        mask[w, union.lookup[v.word_ids]] = True
    return projected, union, mask


def _neg_tables(worker_vocabs: list[Vocab], kind: str = "cdf",
                power: float = 0.75):
    """Stacked per-worker noise tables in the layout ``kind`` draws
    from (see :func:`repro.data.pairs.stack_noise_tables`)."""
    return stack_noise_tables([v.counts for v in worker_vocabs],
                              kind=kind, power=power)


# ---------------------------------------------------------------------------
@dataclass
class TrainingSetup:
    """Everything the train loop needs, derived once from the corpus.

    A pure function of (corpus, strategy, seed, …) — see
    :func:`prepare_training` — so the stacked trainer
    (:func:`train_submodels`) and the per-worker elastic runner
    (:mod:`repro.elastic`) start from identical vocabularies, noise
    tables, pair streams and step schedules."""

    cfg: SGNSConfig              # vocab_size bound to the union vocab
    plan: HostShardPlan
    engine: object               # resolved UpdateEngine
    streams: list                # per-worker WorkerStream (or WalkStream),
                                 # union id space
    union_vocab: Vocab
    mask: np.ndarray             # (n, V_union) presence mask
    neg_table: object            # stacked per-worker noise tables
    sched: object                # EpochSchedule
    batch_size: int
    sentences_per_block: int     # sentences (walks) per extraction block
    seed: int
    epochs: int
    vocab_s: float               # wall-clock of the vocab/noise build


def prepare_training(
    corpus: Corpus,
    raw_vocab_size: int,
    strategy: str,
    num_workers: int,
    cfg: SGNSConfig,
    *,
    epochs: int = 3,
    batch_size: int = 512,
    rate: float | None = None,
    window: int | None = None,
    subsample_t: float | None = 1e-4,
    max_vocab: int | None = 300_000,
    base_min_count: int = 100,
    seed: int = 0,
    max_steps_per_epoch: int | None = None,
    engine="sparse",
    steps_per_chunk: int = 128,
    sentences_per_block: int = 1024,
    process_index: int | None = None,
    process_count: int | None = None,
) -> TrainingSetup:
    """Divide-phase setup shared by the stacked and elastic trainers:
    worker vocabularies (projected into the union id space), stacked
    noise tables in the engine's layout, per-worker pair streams, and
    the epoch schedule sized from a streamed epoch-0 pair count."""
    rate = rate if rate is not None else 1.0 / num_workers
    window = window if window is not None else cfg.window
    engine = get_engine(engine)
    plan = HostShardPlan.for_runtime(num_workers, process_index=process_index,
                                     process_count=process_count)

    t0 = time.perf_counter()
    worker_vocabs, union, mask = build_worker_vocabs(
        corpus, raw_vocab_size, strategy, num_workers, rate,
        max_vocab=max_vocab, base_min_count=base_min_count, seed=seed)
    cfg = SGNSConfig(**{**cfg.__dict__, "vocab_size": union.size})
    neg_table = _neg_tables(worker_vocabs, kind=engine.table_kind)
    vocab_s = time.perf_counter() - t0

    # Pair streams per worker (worker vocab projected into union ids).
    streams = []
    for w in range(num_workers):
        s = make_worker_streams(
            corpus, worker_vocabs[w], num_workers=num_workers, strategy=strategy,
            rate=rate, window=window, subsample_t=subsample_t, seed=seed)[w]
        streams.append(s)

    # Size steps/epoch from a streamed epoch-0 count (O(block) memory —
    # no epoch of pairs is ever materialized; kept equal across workers,
    # shorter streams wrap, as word2vec re-iterates its shard). The count
    # stops as soon as the step cap is known to be reached. Counted over
    # ALL workers on every host: the one-time O(epoch) count is
    # replicated so the schedule is a pure function of (corpus, seed) —
    # no inter-host min-reduction, and every host derives the identical
    # step plan independently.
    count_cap = (None if max_steps_per_epoch is None
                 else max_steps_per_epoch * batch_size)
    min_pairs = min(s.count_pairs(0, sentences_per_block, max_pairs=count_cap)
                    for s in streams)
    if min_pairs == 0:
        raise ValueError("a worker drew an empty sample")
    # One consistent steps/chunks/total_steps derivation (core.schedule):
    # the LR horizon and the chunk loop can't drift apart.
    sched = plan_epoch(min_pairs, batch_size, epochs, steps_per_chunk,
                       max_steps_per_epoch=max_steps_per_epoch)

    return TrainingSetup(
        cfg=cfg, plan=plan, engine=engine, streams=streams,
        union_vocab=union, mask=mask, neg_table=neg_table, sched=sched,
        batch_size=batch_size, sentences_per_block=sentences_per_block,
        seed=seed, epochs=epochs, vocab_s=vocab_s)


def prepare_graph_training(
    graph: Graph,
    num_workers: int,
    cfg: SGNSConfig,
    *,
    epochs: int = 1,
    batch_size: int = 512,
    rate: float | None = None,
    window: int | None = None,
    walks_per_node: int = 80,
    walk_length: int = 40,
    seed: int = 0,
    engine="sparse",
    steps_per_chunk: int = 128,
    walks_per_block: int = 1024,
    process_index: int | None = None,
    process_count: int | None = None,
) -> TrainingSetup:
    """:func:`prepare_training` for a graph (DeepWalk): the rows are the
    graph's nodes in descending degree order, every worker's vocabulary
    is all of them, its noise is degree^0.75, and its stream is a
    :class:`repro.data.graph.WalkStream` of its sample of walk starts.
    The schedule comes from the walk count and the expected pairs per
    walk; no walk is generated here."""
    rate = rate if rate is not None else 1.0 / num_workers
    window = window if window is not None else cfg.window
    engine = get_engine(engine)
    plan = HostShardPlan.for_runtime(num_workers, process_index=process_index,
                                     process_count=process_count)

    t0 = time.perf_counter()
    ordered, order = relabel_by_degree(graph)
    union = degree_vocab(ordered, order)
    cfg = SGNSConfig(**{**cfg.__dict__, "vocab_size": union.size})
    noise = build_noise_table(union.counts, kind=engine.table_kind)
    neg_table = jax.tree.map(lambda a: jnp.stack([a] * num_workers), noise)
    vocab_s = time.perf_counter() - t0

    streams = make_walk_streams(
        ordered, num_workers, rate, walks_per_node=walks_per_node,
        walk_length=walk_length, window=window, seed=seed)
    min_pairs = int(expected_walk_pairs(walk_length, window)
                    * min(s.num_walks for s in streams))
    sched = plan_epoch(min_pairs, batch_size, epochs, steps_per_chunk)
    return TrainingSetup(
        cfg=cfg, plan=plan, engine=engine, streams=streams,
        union_vocab=union, mask=np.ones((num_workers, union.size), bool),
        neg_table=neg_table, sched=sched, batch_size=batch_size,
        sentences_per_block=walks_per_block, seed=seed, epochs=epochs,
        vocab_s=vocab_s)


@dataclass
class PipelineResult:
    strategy: str
    num_workers: int
    union_vocab: Vocab
    stacked: StackedModels
    merged: dict = field(default_factory=dict)       # method -> (emb, valid)
    timings: dict = field(default_factory=dict)
    losses: list = field(default_factory=list)


def train_submodels(
    corpus: Corpus,
    raw_vocab_size: int,
    strategy: str,
    num_workers: int,
    cfg: SGNSConfig,
    epochs: int = 3,
    batch_size: int = 512,
    rate: float | None = None,
    window: int | None = None,
    subsample_t: float | None = 1e-4,
    max_vocab: int | None = 300_000,
    base_min_count: int = 100,
    backend: str = "vmap",
    mesh=None,
    seed: int = 0,
    max_steps_per_epoch: int | None = None,
    engine="sparse",
    steps_per_chunk: int = 128,
    prefetch: int = 2,
    sentences_per_block: int = 1024,
    process_index: int | None = None,
    process_count: int | None = None,
) -> PipelineResult:
    """``process_index`` / ``process_count`` (default: the jax runtime's)
    select multi-host ingestion: this host extracts only its
    :class:`HostShardPlan` block of workers' chunk streams and the
    global device arrays are assembled from the per-process blocks.
    Everything per-host is a pure function of the plan, so any host
    count can be simulated in one process (``tests/test_multihost.py``);
    with ``process_count == 1`` the path is bit-identical to the
    single-host stream."""
    setup = prepare_training(
        corpus, raw_vocab_size, strategy, num_workers, cfg,
        epochs=epochs, batch_size=batch_size, rate=rate, window=window,
        subsample_t=subsample_t, max_vocab=max_vocab,
        base_min_count=base_min_count, seed=seed,
        max_steps_per_epoch=max_steps_per_epoch, engine=engine,
        steps_per_chunk=steps_per_chunk,
        sentences_per_block=sentences_per_block,
        process_index=process_index, process_count=process_count)
    return train_prepared(setup, strategy=strategy, backend=backend,
                          mesh=mesh, prefetch=prefetch)


def train_prepared(setup: TrainingSetup, *, strategy: str = "random",
                   backend: str = "vmap", mesh=None,
                   prefetch: int = 2) -> PipelineResult:
    """The train loop over a prepared :class:`TrainingSetup` (text or
    graph): this host's chunk stream, prefetched, through
    :meth:`AsyncShardTrainer.epoch` for every chunk of every epoch."""
    plan = setup.plan
    multihost = plan.process_count > 1
    if multihost:
        if backend != "shard_map" or mesh is None:
            raise ValueError(
                "multi-host ingestion (process_count > 1) requires "
                "backend='shard_map' and a mesh")
        plan.validate_for_mesh(mesh)
    cfg, engine, sched = setup.cfg, setup.engine, setup.sched
    streams, union, mask = setup.streams, setup.union_vocab, setup.mask
    neg_table, t_vocab = setup.neg_table, setup.vocab_s
    num_workers, seed = plan.num_workers, setup.seed

    trainer = AsyncShardTrainer(
        cfg=cfg, num_workers=num_workers, total_steps=sched.total_steps,
        backend=backend, mesh=mesh, engine=engine,
        plan=plan if multihost else None)
    params = trainer.init(jax.random.PRNGKey(cfg.seed))
    if multihost:
        # Each host contributes only its own workers' noise-table rows.
        neg_table = trainer.device_table(
            jax.tree.map(lambda a: np.asarray(a)[plan.start:plan.stop],
                         neg_table))

    # This host's ingestion: only its plan block of worker streams is
    # ever extracted (single-host: the block is all workers).
    chunk_stream = plan.chunk_stream(
        streams, batch_size=setup.batch_size,
        steps_per_chunk=sched.chunk_steps,
        sentences_per_block=setup.sentences_per_block)

    losses = []
    t_train0 = time.perf_counter()
    for epoch in range(setup.epochs):
        ep_key = _epoch_key(seed, _STREAM_ASYNC_DATA, epoch)
        ep_losses = []
        # Host extraction + H2D copy of chunk k+1 overlap the device's
        # work on chunk k (async dispatch; queue depth = `prefetch`).
        # Multi-host, the transfer is the per-chunk global assembly
        # (make_array_from_process_local_data), done on the main thread.
        chunk_it = prefetch_chunks(
            chunk_stream.chunks(epoch, sched.num_chunks), depth=prefetch,
            to_device=not multihost)
        for k, (centers, contexts) in enumerate(chunk_it):
            if multihost:
                centers, contexts = trainer.device_chunk(centers, contexts)
            params, chunk_losses = trainer.epoch(
                params, centers, contexts, neg_table,
                jax.random.fold_in(ep_key, k),
                step0=sched.step0(epoch, k),
            )
            ep_losses.append(chunk_losses)
        losses.append(float(_mean_loss(ep_losses)))
    jax.block_until_ready(params)
    t_train = time.perf_counter() - t_train0

    stacked = StackedModels(models=params["W"], mask=jnp.asarray(mask))
    return PipelineResult(
        strategy=strategy, num_workers=num_workers, union_vocab=union,
        stacked=stacked, timings={"vocab_s": t_vocab, "train_s": t_train,
                                  "steps_per_epoch": sched.steps_per_epoch},
        losses=losses)


def run_pipeline(
    corpus: Corpus,
    raw_vocab_size: int,
    strategy: str = "shuffle",
    num_workers: int = 10,
    cfg: SGNSConfig | None = None,
    merge_methods: tuple[str, ...] = ("concat", "pca", "alir_pca"),
    merge_fan_in: int = 2,
    merge_shard: int = 1,
    **kw,
) -> PipelineResult:
    cfg = cfg or SGNSConfig(vocab_size=0, dim=64)
    res = train_submodels(corpus, raw_vocab_size, strategy, num_workers, cfg, **kw)
    return apply_merges(res, merge_methods, out_dim=cfg.dim,
                        fan_in=merge_fan_in, shard=merge_shard)


def apply_merges(res: PipelineResult, merge_methods, out_dim: int, *,
                 fan_in: int = 2, shard: int = 1) -> PipelineResult:
    """Merge-phase tail shared by :func:`run_pipeline` and the elastic
    launcher: fold the stacked sub-models with each requested method,
    recording wall-clock per method in ``res.timings``. ``fan_in``
    sizes the ``alir_tree`` reduction tree; ``shard`` the ALiR Gram
    accumulation (both static dials, see :mod:`repro.core.merge`)."""
    for method in merge_methods:
        t0 = time.perf_counter()
        emb, valid = merge_models(res.stacked, method, out_dim=out_dim,
                                  key=jax.random.PRNGKey(42),
                                  fan_in=fan_in, shard=shard)
        jax.block_until_ready(emb)
        res.merged[method] = (np.asarray(emb), np.asarray(valid))
        res.timings[f"merge_{method}_s"] = time.perf_counter() - t0
    return res


# ---------------------------------------------------------------------------
# Synchronized baseline (the paper's Hogwild stand-in) end-to-end.
# ---------------------------------------------------------------------------
def train_sync_baseline(
    corpus: Corpus,
    raw_vocab_size: int,
    cfg: SGNSConfig,
    epochs: int = 3,
    batch_size: int = 512,
    window: int | None = None,
    subsample_t: float | None = 1e-4,
    max_vocab: int | None = 300_000,
    seed: int = 0,
    max_steps_per_epoch: int | None = None,
    mesh=None,
    engine="dense",
):
    from repro.data.pairs import extract_pairs

    engine = get_engine(engine)
    vocab = build_vocab(corpus, raw_vocab_size, min_count=1, max_size=max_vocab)
    cfg = SGNSConfig(**{**cfg.__dict__, "vocab_size": vocab.size})
    window = window if window is not None else cfg.window
    neg_table = _neg_tables([vocab], kind=engine.table_kind)
    # single-model: drop the stacked leading worker axis
    neg_table = jax.tree.map(lambda a: a[0], neg_table)

    centers, contexts = extract_pairs(corpus, vocab, window=window,
                                      subsample_t=subsample_t, seed=seed)
    steps = max(1, len(centers) // batch_size)
    if max_steps_per_epoch is not None:
        steps = min(steps, max_steps_per_epoch)
    total_steps = steps * epochs
    epoch_fn = make_sync_epoch(cfg, neg_table, total_steps, mesh=mesh,
                               engine=engine)

    from repro.core import sgns as sgns_mod
    params = sgns_mod.init_params(jax.random.PRNGKey(cfg.seed), cfg)
    need = steps * batch_size
    losses = []
    t0 = time.perf_counter()
    for epoch in range(epochs):
        rng = _epoch_rng(seed, _STREAM_SYNC_PERM, epoch)
        perm = _tiled_permutation(rng, len(centers), need)
        c = jnp.asarray(centers[perm].reshape(steps, batch_size))
        x = jnp.asarray(contexts[perm].reshape(steps, batch_size))
        params, ep_losses = epoch_fn(params, c, x,
                                     _epoch_key(seed, _STREAM_SYNC_EPOCH,
                                                epoch),
                                     jnp.int32(epoch * steps))
        losses.append(float(jnp.mean(ep_losses)))
    jax.block_until_ready(params)
    return params, vocab, {"train_s": time.perf_counter() - t0,
                           "steps_per_epoch": steps, "losses": losses}
