"""Per-worker sample streams — the paper's mapper/reducer, TPU-style.

In the paper a MapReduce mapper assigns each sentence to each of the
``n`` sub-corpora independently with probability ``r/100`` and ships it
to the matching reducer. Sampling with replacement is *stateless*, so on
a TPU pod we invert control: each worker draws its own sample directly
from the (shared, read-only) corpus with a deterministic PRNG stream —
``seed = hash(worker, epoch)`` for Shuffle, ``hash(worker)`` for fixed
RANDOM SAMPLING. No shuffle network phase exists at all.
"""

from __future__ import annotations

import itertools
import queue
import threading
from dataclasses import dataclass
from typing import Iterator, Sequence

import numpy as np

from repro import obs
from repro.data.corpus import Corpus
from repro.data.vocab import Vocab
from repro.data.pairs import extract_pairs
from repro.core.sampling import sample_sentence_indices

# Pair-extraction RNG streams. Domain-tagged SeedSequence tuples: the
# leading constant keeps this module's streams disjoint from every
# other module's numpy seeding (e.g. the driver's epoch streams), and
# the whole-epoch/per-block sub-tag keeps those two paths disjoint from
# each other — SeedSequence absorbs trailing zero words, so the naive
# (seed, worker, epoch) vs (seed, worker, epoch, 0) pair would collide.
# The old arithmetic seeds (seed*7919 + worker*104729 + epoch) aliased
# across distinct (seed, worker, epoch) outright.
_SEED_DOMAIN = 0x91BE       # pipeline pair extraction
_SUB_EPOCH, _SUB_BLOCK = 0, 1


def _extract_seed(seed: int, worker: int, epoch: int,
                  block: int | None = None) -> np.random.SeedSequence:
    if block is None:
        return np.random.SeedSequence(
            (_SEED_DOMAIN, _SUB_EPOCH, seed, worker, epoch))
    return np.random.SeedSequence(
        (_SEED_DOMAIN, _SUB_BLOCK, seed, worker, epoch, block))


@dataclass
class WorkerStream:
    """One sub-model's training stream for one epoch."""

    corpus: Corpus
    vocab: Vocab
    worker: int
    strategy: str           # 'equal' | 'random' | 'shuffle'
    rate: float             # sampling rate r in (0, 1]
    num_workers: int
    window: int = 10
    subsample_t: float | None = 1e-4
    seed: int = 0

    def sentence_indices(self, epoch: int) -> np.ndarray:
        """This worker's sentence sample for ``epoch`` — deterministic in
        ``(seed, worker, epoch)`` per the division strategy (EQUAL keeps
        a fixed contiguous slice, RANDOM a fixed with-replacement draw,
        SHUFFLE a fresh draw per epoch)."""
        return sample_sentence_indices(
            num_sentences=self.corpus.num_sentences,
            strategy=self.strategy,
            rate=self.rate,
            worker=self.worker,
            num_workers=self.num_workers,
            epoch=epoch,
            seed=self.seed,
        )

    def pairs(self, epoch: int, max_pairs: int | None = None):
        """All of this worker's ``(centers, contexts)`` pairs for
        ``epoch``, materialized in one pass (epoch-sized host memory —
        prefer :meth:`pair_blocks` / :class:`PairChunkStream` for large
        corpora). ``max_pairs`` truncates extraction early."""
        idx = self.sentence_indices(epoch)
        sub = self.corpus.select(idx)
        return extract_pairs(
            sub,
            self.vocab,
            window=self.window,
            subsample_t=self.subsample_t,
            seed=_extract_seed(self.seed, self.worker, epoch),
            max_pairs=max_pairs,
        )

    def pair_blocks(
        self, epoch: int, sentences_per_block: int = 1024
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Stream (centers, contexts) per sentence-block instead of
        materializing the whole epoch.

        Windows never cross sentence boundaries, so block-wise extraction
        yields the same pair *set* as :meth:`pairs` — only the RNG stream
        (subsampling, dynamic windows) and the shuffle scope (within a
        block rather than global) differ. Peak host memory is one block's
        pairs, independent of corpus size. Deterministic in
        (seed, worker, epoch, block).
        """
        idx = self.sentence_indices(epoch)
        for b, start in enumerate(range(0, len(idx), sentences_per_block)):
            sub = self.corpus.select(idx[start : start + sentences_per_block])
            c, x = extract_pairs(
                sub,
                self.vocab,
                window=self.window,
                subsample_t=self.subsample_t,
                seed=_extract_seed(self.seed, self.worker, epoch, block=b),
            )
            if len(c):
                yield c, x

    def count_pairs(self, epoch: int, sentences_per_block: int = 1024,
                    max_pairs: int | None = None) -> int:
        """Number of pairs the block stream yields for ``epoch``, counted
        block-by-block in O(block) memory (no epoch materialization).
        Stops early once ``max_pairs`` is reached — callers sizing a
        capped epoch don't pay for counting the tail."""
        total = 0
        for c, _ in self.pair_blocks(epoch, sentences_per_block):
            total += len(c)
            if max_pairs is not None and total >= max_pairs:
                break
        return total

    def batches(
        self, epoch: int, batch_size: int, max_pairs: int | None = None
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Full-batch slices of :meth:`pairs` (the materialized path;
        the trailing partial batch is dropped)."""
        centers, contexts = self.pairs(epoch, max_pairs=max_pairs)
        n = (len(centers) // batch_size) * batch_size
        for i in range(0, n, batch_size):
            yield centers[i : i + batch_size], contexts[i : i + batch_size]


def make_worker_streams(
    corpus: Corpus,
    vocab: Vocab,
    num_workers: int,
    strategy: str,
    rate: float | None = None,
    **kw,
) -> list[WorkerStream]:
    """One :class:`WorkerStream` per worker (ordered by worker id — the
    order :meth:`HostShardPlan.local_streams` validates against), all
    sharing the corpus/vocab and the division ``strategy``. ``rate``
    defaults to the paper's ``1/num_workers``; extra kwargs (``window``,
    ``subsample_t``, ``seed``) pass through to every stream."""
    rate = rate if rate is not None else 1.0 / num_workers
    return [
        WorkerStream(
            corpus=corpus,
            vocab=vocab,
            worker=w,
            strategy=strategy,
            rate=rate,
            num_workers=num_workers,
            **kw,
        )
        for w in range(num_workers)
    ]


@dataclass
class PairChunkStream:
    """Streaming, fixed-shape chunk producer for the async shard trainer.

    Replaces the materialize-everything path: instead of extracting one
    giant per-epoch pair array and ``np.tile``-ing it, each worker's
    epoch is consumed block-of-sentences at a time
    (:meth:`WorkerStream.pair_blocks`) and packed into
    ``(n_workers, steps_per_chunk, batch)`` buffers whose shape never
    changes — so the trainer compiles once and host memory stays
    O(n_workers · chunk + block), independent of corpus size.

    Workers whose epoch runs dry wrap around (the block stream is
    deterministic, so a wrap replays the same pairs — exactly the old
    ``np.tile`` semantics); sub-models stay perfectly load-balanced.
    """

    streams: list[WorkerStream]
    batch_size: int
    steps_per_chunk: int
    sentences_per_block: int = 1024

    @property
    def num_workers(self) -> int:
        """Number of worker streams feeding this chunk stream."""
        return len(self.streams)

    @property
    def chunk_pairs(self) -> int:
        """Pairs each worker contributes per chunk
        (``batch_size * steps_per_chunk``)."""
        return self.batch_size * self.steps_per_chunk

    def chunks(
        self, epoch: int, num_chunks: int | None = None,
        start_chunk: int = 0,
    ) -> Iterator[tuple[np.ndarray, np.ndarray]]:
        """Yield (centers, contexts) arrays of shape
        (n_workers, steps_per_chunk, batch) for chunk indices
        ``[start_chunk, num_chunks)`` of this epoch's stream (infinite
        tail when ``num_chunks`` is ``None``).

        ``start_chunk`` is the elastic-resume fast-forward: the first
        ``start_chunk`` chunks are *extracted and discarded* through
        exactly the buffer-fill path a yielded chunk takes (same
        generators, same wrap-arounds, same slicing), so the yielded
        tail is bit-identical to the corresponding suffix of the
        uninterrupted ``chunks(epoch, num_chunks)`` stream — a resumed
        worker replays its stream exactly. The fast-forward costs pair
        *extraction* only (no output assembly, no device transfer);
        ``benchmarks/bench_elastic.py`` tracks that overhead.
        """
        if start_chunk < 0:
            raise ValueError(f"start_chunk must be >= 0, got {start_chunk}")
        if num_chunks is not None and start_chunk > num_chunks:
            raise ValueError(
                f"start_chunk {start_chunk} past the stream's "
                f"num_chunks {num_chunks}")
        n, need = self.num_workers, self.chunk_pairs
        gens = [s.pair_blocks(epoch, self.sentences_per_block)
                for s in self.streams]
        bufs: list[list[np.ndarray]] = [[] for _ in range(n)]
        xufs: list[list[np.ndarray]] = [[] for _ in range(n)]
        have = [0] * n
        pass_pairs = [0] * n   # pairs seen since this worker's last wrap

        def fill_and_cut(w: int, centers=None, contexts=None) -> None:
            # Advance worker w's buffers by exactly one chunk's worth of
            # pairs; write the chunk rows out only when asked. Skipped
            # (fast-forward) and yielded chunks share this path, which
            # is what makes the resume replay bit-exact.
            while have[w] < need:
                try:
                    c, x = next(gens[w])
                except StopIteration:
                    if pass_pairs[w] == 0:
                        raise ValueError(
                            f"worker {w} epoch {epoch}: empty sample")
                    pass_pairs[w] = 0
                    gens[w] = self.streams[w].pair_blocks(
                        epoch, self.sentences_per_block)
                    continue
                bufs[w].append(c)
                xufs[w].append(x)
                have[w] += len(c)
                pass_pairs[w] += len(c)
            flat_c = np.concatenate(bufs[w])
            flat_x = np.concatenate(xufs[w])
            if centers is not None:
                centers[w] = flat_c[:need]
                contexts[w] = flat_x[:need]
            bufs[w] = [flat_c[need:]]
            xufs[w] = [flat_x[need:]]
            have[w] -= need

        done = 0
        while done < start_chunk:
            for w in range(n):
                fill_and_cut(w)
            done += 1
        while num_chunks is None or done < num_chunks:
            with obs.span(obs.INGEST_EXTRACT, chunk=done):
                centers = np.empty((n, need), dtype=np.int32)
                contexts = np.empty((n, need), dtype=np.int32)
                for w in range(n):
                    fill_and_cut(w, centers, contexts)
            shape = (n, self.steps_per_chunk, self.batch_size)
            yield centers.reshape(shape), contexts.reshape(shape)
            done += 1


# ---------------------------------------------------------------------------
# Multi-host ingestion planning.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HostShardPlan:
    """Which workers' chunk streams THIS host extracts.

    The paper scales by partitioning the *input*, not the parameters:
    each worker's sample stream is a pure function of
    ``(seed, worker, epoch)``, so a host needs nothing but its worker
    ids to reproduce exactly the chunks a single-host run would have
    produced for those workers. The plan is therefore a pure value —
    no jax state, no communication — which is what lets tests simulate
    any ``process_count`` inside one process and assert bit-identity
    against the single-host stream.

    Workers are block-partitioned contiguously and as evenly as
    possible: host ``p`` owns ``[p·W//P, (p+1)·W//P)``. Contiguity
    matters — it matches jax's row-major device order for a 1-D
    ``worker`` mesh axis, so each host's extracted block is exactly the
    process-local shard :func:`jax.make_array_from_process_local_data`
    expects (see ``repro.launch.mesh.assemble_worker_array``).
    """

    process_index: int
    process_count: int
    num_workers: int

    def __post_init__(self):
        if self.process_count < 1:
            raise ValueError(f"process_count must be >= 1, got {self.process_count}")
        if not (0 <= self.process_index < self.process_count):
            raise ValueError(
                f"process_index {self.process_index} outside "
                f"[0, {self.process_count})")
        if self.num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {self.num_workers}")

    # -------------------------------------------------- worker ownership
    @property
    def start(self) -> int:
        """First global worker id this host owns (inclusive)."""
        return (self.process_index * self.num_workers) // self.process_count

    @property
    def stop(self) -> int:
        """One past the last global worker id this host owns."""
        return ((self.process_index + 1) * self.num_workers) // self.process_count

    @property
    def workers(self) -> range:
        """Global worker ids this host owns (possibly empty when there
        are more hosts than workers)."""
        return range(self.start, self.stop)

    @property
    def num_local(self) -> int:
        """How many workers this host owns."""
        return self.stop - self.start

    # ------------------------------------------------------ construction
    @classmethod
    def for_runtime(cls, num_workers: int, process_index: int | None = None,
                    process_count: int | None = None) -> "HostShardPlan":
        """Plan for the current jax runtime; either field can be pinned
        explicitly (that is the whole single-host simulation story)."""
        import jax

        if process_count is None:
            process_count = jax.process_count()
        if process_index is None:
            process_index = jax.process_index()
        return cls(process_index=process_index, process_count=process_count,
                   num_workers=num_workers)

    @classmethod
    def all_hosts(cls, process_count: int,
                  num_workers: int) -> list["HostShardPlan"]:
        """One plan per simulated host — the test harness's entry point."""
        return [cls(p, process_count, num_workers)
                for p in range(process_count)]

    # ------------------------------------------------------- local views
    def local_streams(self, streams: Sequence[WorkerStream]
                      ) -> list[WorkerStream]:
        """This host's slice of the global per-worker stream list."""
        if len(streams) != self.num_workers:
            raise ValueError(
                f"plan covers {self.num_workers} workers, got "
                f"{len(streams)} streams")
        for w, s in zip(self.workers, streams[self.start:self.stop]):
            if s.worker != w:
                raise ValueError(
                    f"stream at global position {w} claims worker "
                    f"{s.worker}; streams must be ordered by worker id")
        return list(streams[self.start:self.stop])

    def chunk_stream(self, streams: Sequence[WorkerStream], *,
                     batch_size: int, steps_per_chunk: int,
                     sentences_per_block: int = 1024) -> PairChunkStream:
        """The host-local :class:`PairChunkStream`: chunks of shape
        ``(num_local, steps_per_chunk, batch)`` whose worker-axis
        concatenation over all hosts is bit-identical to the single-host
        stream over the same ``streams``."""
        return PairChunkStream(
            self.local_streams(streams), batch_size=batch_size,
            steps_per_chunk=steps_per_chunk,
            sentences_per_block=sentences_per_block)

    # -------------------------------------------------------- validation
    def validate_for_mesh(self, mesh) -> None:
        """Check the plan can assemble global arrays on ``mesh``: a
        ``worker`` axis spanning exactly ``num_workers`` positions, and
        even per-process blocks (``make_array_from_process_local_data``
        requires equal-shaped process-local shards)."""
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if "worker" not in axis_sizes:
            raise ValueError(
                f"mesh has no 'worker' axis (axes: {mesh.axis_names})")
        if self.num_workers % axis_sizes["worker"] != 0:
            raise ValueError(
                f"num_workers={self.num_workers} not divisible by the "
                f"worker axis size {axis_sizes['worker']}")
        if self.num_workers % self.process_count != 0:
            raise ValueError(
                f"num_workers={self.num_workers} must divide evenly over "
                f"{self.process_count} processes for per-host block "
                f"sharding (got uneven blocks)")

    def describe(self) -> str:
        """One-line plan summary (the dryrun CLI's printout)."""
        return (f"host {self.process_index}/{self.process_count}: "
                f"workers [{self.start}, {self.stop}) "
                f"({self.num_local} of {self.num_workers})")


_SENTINEL = object()


def prefetch_chunks(iterator, depth: int = 2, to_device: bool = True):
    """Double-buffered prefetch: a background thread extracts the next
    chunk(s) and (optionally) dispatches the host→device transfer while
    the caller's device computation runs — jax dispatch is asynchronous,
    so ``jnp.asarray`` here starts the copy without blocking on it.

    ``depth`` bounds the queue, so at most ``depth`` chunks are ever
    resident beyond the one being consumed.

    Producer-thread lifecycle guarantees (regression-tested in
    ``tests/test_streaming.py``):

    * an exception anywhere in the producer (extraction or the device
      transfer) is delivered to the consumer and re-raised — including
      when the queue is full at the time it is raised;
    * abandoning the generator (``close()`` / ``break`` / consumer
      exception) releases and **joins** the producer thread — it never
      outlives the generator blocked on the bounded queue;
    * a producer thread that dies without delivering its sentinel or
      exception surfaces as ``RuntimeError`` instead of hanging the
      consumer's blocking ``get`` forever.
    """
    if depth < 1:
        raise ValueError("prefetch depth must be >= 1")
    # validation above is eager (plain function); the lazy generator
    # below owns the thread lifecycle
    return _prefetch_gen(iterator, depth, to_device)


def _get(q: queue.Queue, thread: threading.Thread):
    """The next item of the queue. Bounded get + liveness check: if the
    producer thread dies without enqueuing its sentinel/exception
    (interpreter teardown, thread killed), the consumer must error out,
    not block forever on an empty queue."""
    while True:
        try:
            return q.get(timeout=0.5)
        except queue.Empty:
            if not thread.is_alive():
                raise RuntimeError(
                    "prefetch_chunks producer thread died without "
                    "delivering a chunk, sentinel or exception")


def _prefetch_gen(iterator, depth: int, to_device: bool):
    import jax.numpy as jnp

    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def put(item) -> bool:
        # Bounded put that gives up when the consumer abandons the
        # generator — otherwise the thread would block forever holding
        # up to `depth` device-resident chunks.
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def produce():
        try:
            for k, item in enumerate(iterator):
                if to_device:
                    with obs.span(obs.INGEST_H2D, chunk=k):
                        item = tuple(jnp.asarray(a) for a in item)
                if not put(item):
                    return
            put(_SENTINEL)
        except BaseException as e:  # surface extraction errors to the consumer
            put(e)

    thread = threading.Thread(target=produce, daemon=True,
                              name="prefetch_chunks")
    thread.start()
    try:
        for k in itertools.count():
            with obs.span(obs.INGEST_WAIT, chunk=k):
                item = _get(q, thread)
            if item is _SENTINEL:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # Unblock a producer waiting on the full queue, then reap the
        # thread: at most one more item can land after the drain (put()
        # re-checks `stop` before each attempt), so join cannot block on
        # queue capacity.
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        thread.join(timeout=5.0)


def stacked_pair_batches(
    streams: list[WorkerStream],
    epoch: int,
    batch_size: int,
    num_batches: int,
) -> tuple[np.ndarray, np.ndarray]:
    """(n_workers, num_batches, batch) arrays for the async shard trainer.

    Materialized view of :class:`PairChunkStream` — one chunk covering
    the whole request, so streamed and materialized consumers see
    byte-identical batches for the same seed.
    """
    stream = PairChunkStream(streams, batch_size=batch_size,
                             steps_per_chunk=num_batches)
    return next(stream.chunks(epoch, num_chunks=1))
