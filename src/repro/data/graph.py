"""Graph input: an undirected CSR graph, node ids ordered by degree, and
per-worker streams of random-walk skip-gram pairs (DeepWalk, Perozzi et
al., KDD 2014, arXiv 1403.6652).

DeepWalk trains SGNS on the node sequences of truncated random walks:
γ walks start at every node, each ``t`` nodes long, each step to a
neighbour drawn uniformly. The system trains a graph the way it trains
text (arXiv 1812.03825): each of ``n`` workers takes a RANDOM SAMPLING
of the γ·|V| walk starts and trains its own sub-model on its walks,
with no collective.

A walk corpus is γ·t·|V| tokens, too large to hold (14.6 GB of int32
at YouTube's scale), so nothing here materialises it: a worker's
:meth:`WalkStream.pair_blocks` generates each block's walks on the fly,
cuts their pairs and yields them, holding one block on the host.

Rows are the graph's nodes renumbered by descending degree
(:func:`relabel_by_degree`): a walk visits a node at a rate
proportional to its degree, so the id prefix holds the hottest rows, as
``data/vocab.py``'s frequency order does for words, and the noise
distribution is degree^0.75 without a counting pass over any walk.

``python -m repro.data.graph --out edges.npz`` writes a seeded
Chung–Lu graph (:func:`chung_lu_edges`) as an edge list for
``repro.launch.train_sgns --graph``.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import numpy as np

from repro import obs
from repro.data.pairs import window_pairs
from repro.data.vocab import Vocab

# Domain-tagged SeedSequence tuples (see data/pipeline.py): the leading
# constant keeps the walk sources' numpy streams disjoint from every
# other module's, and the sub-tag keeps the start sample's stream
# disjoint from the per-block walk streams.
_SEED_DOMAIN = 0x6EA1       # graph walk sources
_SUB_START, _SUB_WALK = 0, 1
_GEN_DOMAIN = 0x6EA2        # the Chung–Lu generator


@dataclass(frozen=True)
class Graph:
    """An undirected simple graph in CSR form: node ``u``'s neighbours
    are ``indices[indptr[u]:indptr[u + 1]]``, ascending; every edge is
    listed from both of its ends."""

    indptr: np.ndarray      # (V+1,) int64
    indices: np.ndarray     # (2E,) int32

    @property
    def num_nodes(self) -> int:
        return len(self.indptr) - 1

    @property
    def num_edges(self) -> int:
        return len(self.indices) // 2

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    @classmethod
    def from_edges(cls, src, dst, num_nodes: int) -> "Graph":
        """The simple graph on an edge list: ids must lie in
        ``[0, num_nodes)``; an edge listed twice (in either direction)
        counts once, and self-loops are dropped."""
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if src.shape != dst.shape or src.ndim != 1:
            raise ValueError("src and dst must be 1-D arrays of one length")
        if len(src) and (min(src.min(), dst.min()) < 0
                         or max(src.max(), dst.max()) >= num_nodes):
            raise ValueError(f"edge ids must lie in [0, {num_nodes})")
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        keys = np.unique((lo * num_nodes + hi)[lo != hi])
        lo, hi = keys // num_nodes, keys % num_nodes
        a, b = np.concatenate([lo, hi]), np.concatenate([hi, lo])
        order = np.lexsort((b, a))
        indptr = np.zeros(num_nodes + 1, dtype=np.int64)
        np.cumsum(np.bincount(a, minlength=num_nodes), out=indptr[1:])
        return cls(indptr=indptr, indices=b[order].astype(np.int32))

    def edges(self) -> tuple[np.ndarray, np.ndarray]:
        """Each edge once, as ``(u, v)`` with ``u < v``."""
        u = np.repeat(np.arange(self.num_nodes, dtype=np.int64),
                      self.degrees)
        keep = u < self.indices
        return u[keep], self.indices[keep].astype(np.int64)

    def relabel(self, order: np.ndarray) -> "Graph":
        """The same graph with node ``order[i]`` renamed ``i``."""
        rank = np.empty(self.num_nodes, dtype=np.int64)
        rank[order] = np.arange(self.num_nodes)
        u, v = self.edges()
        return Graph.from_edges(rank[u], rank[v], self.num_nodes)

    def save(self, path) -> None:
        """An edge-list ``.npz`` (``src``, ``dst``, ``num_nodes``)."""
        u, v = self.edges()
        np.savez(path, src=u, dst=v, num_nodes=self.num_nodes)

    @classmethod
    def load(cls, path) -> "Graph":
        with np.load(path) as f:
            return cls.from_edges(f["src"], f["dst"], int(f["num_nodes"]))


def relabel_by_degree(graph: Graph) -> tuple[Graph, np.ndarray]:
    """``graph`` renumbered by descending degree, ties by node id, and
    the order: row ``i`` of the result is node ``order[i]``."""
    order = np.argsort(-graph.degrees, kind="stable")
    return graph.relabel(order), order


def degree_vocab(graph: Graph, order: np.ndarray) -> Vocab:
    """The rows of a degree-ordered graph as a vocabulary: row ``i`` is
    the original node ``order[i]`` and counts its degree (a walk's
    stationary visit rate is proportional to it)."""
    lookup = np.empty(graph.num_nodes, dtype=np.int32)
    lookup[order] = np.arange(graph.num_nodes, dtype=np.int32)
    return Vocab(word_ids=np.asarray(order, dtype=np.int32),
                 counts=graph.degrees.astype(np.int64), lookup=lookup)


def random_walks(graph: Graph, starts: np.ndarray, length: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``(len(starts), length)`` walks, all advanced together: at each
    step every walk moves to neighbour ``rng.integers(0, degree)`` of
    its node. Every start must have a neighbour."""
    walks = np.empty((len(starts), length), dtype=np.int32)
    cur = np.asarray(starts, dtype=np.int64)
    walks[:, 0] = cur
    deg = graph.degrees
    for step in range(1, length):
        cur = graph.indices[graph.indptr[cur] + rng.integers(0, deg[cur])]
        walks[:, step] = cur
    return walks


def walk_pairs(walks: np.ndarray, window: int,
               rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """The skip-gram pairs of a block of walks, cut by word2vec's rule
    (:func:`repro.data.pairs.window_pairs`) with each walk a sentence and
    no subsampling."""
    n, t = walks.shape
    sent = np.repeat(np.arange(n, dtype=np.int64), t)
    return window_pairs(walks.ravel(), sent, window, rng)


def expected_walk_pairs(walk_length: int, window: int) -> float:
    """Expected pairs one walk yields: a pair at offset ``k`` exists in
    each direction when the window drawn at its centre reaches ``k``,
    with probability ``(window - k + 1) / window``."""
    return sum(2.0 * (walk_length - k) * (window - k + 1) / window
               for k in range(1, min(window, walk_length - 1) + 1))


@dataclass
class WalkStream:
    """One sub-model's stream of random-walk pairs over a graph.

    The sample is RANDOM SAMPLING of walk starts: of the
    ``walks_per_node · A`` starts (``A`` the nodes with a neighbour),
    the worker draws ``round(rate · walks_per_node · A)`` with
    replacement, the same in every epoch; start ``s`` is the
    ``(s mod A)``-th node with a neighbour. Its walks and pairs come
    block by block, seeded by ``(seed, worker, epoch, block)``."""

    graph: Graph            # the CSR the walks run on (degree-ordered)
    worker: int
    rate: float
    num_workers: int
    walks_per_node: int = 80
    walk_length: int = 40
    window: int = 10
    seed: int = 0

    def _starts_space(self) -> tuple[np.ndarray, int]:
        active = np.flatnonzero(self.graph.degrees > 0)
        total = self.walks_per_node * len(active)
        return active, max(1, int(round(self.rate * total)))

    @property
    def num_walks(self) -> int:
        """Walks in this worker's sample (without drawing it)."""
        return self._starts_space()[1]

    def walk_starts(self) -> np.ndarray:
        """This worker's walk start nodes, in sample order."""
        active, target = self._starts_space()
        rng = np.random.default_rng(np.random.SeedSequence(
            (_SEED_DOMAIN, _SUB_START, self.seed, self.worker)))
        s = rng.integers(0, self.walks_per_node * len(active), size=target,
                         dtype=np.int64)
        return active[s % len(active)].astype(np.int32)

    def pair_blocks(self, epoch: int, walks_per_block: int = 1024):
        """``(centers, contexts)`` per block of ``walks_per_block`` walks
        of the sample, in order: the block's walks, then its pairs, from
        one generator seeded by ``(seed, worker, epoch, block)``. Each
        block is timed under the host span ``repro.ingest.walk`` and
        counted (``graph.walk_ns``, ``graph.pairs``)."""
        starts = self.walk_starts()
        for b, lo in enumerate(range(0, len(starts), walks_per_block)):
            with obs.span(obs.INGEST_WALK, block=b):
                t0 = time.perf_counter_ns()
                rng = np.random.default_rng(np.random.SeedSequence(
                    (_SEED_DOMAIN, _SUB_WALK, self.seed, self.worker, epoch,
                     b)))
                walks = random_walks(self.graph,
                                     starts[lo:lo + walks_per_block],
                                     self.walk_length, rng)
                c, x = walk_pairs(walks, self.window, rng)
                obs.count(obs.WALK_NS, time.perf_counter_ns() - t0)
                obs.count(obs.GRAPH_PAIRS, len(c))
            if len(c):
                yield c, x


def make_walk_streams(graph: Graph, num_workers: int, rate: float,
                      **kw) -> list[WalkStream]:
    """One :class:`WalkStream` per worker, ordered by worker id (the
    order :meth:`repro.data.pipeline.HostShardPlan.local_streams`
    checks); ``kw`` (walks_per_node, walk_length, window, seed) passes
    to every stream."""
    return [WalkStream(graph=graph, worker=w, rate=rate,
                       num_workers=num_workers, **kw)
            for w in range(num_workers)]


# ---------------------------------------------------------------------------
# A seeded graph with a power-law degree law, for tests, examples and the
# graph quickstart.
# ---------------------------------------------------------------------------
def chung_lu_edges(seed: int, num_nodes: int, num_edges: int,
                   max_degree: float, exponent: float = 2.2
                   ) -> tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` of a simple graph with exactly ``num_edges`` edges
    in which every node has a neighbour: Chung–Lu with power-law
    expected degrees.

    * Expected degrees ``w_i ∝ (i + i0)^(-1/(exponent-1))``, with ``i0``
      set so the largest is ``max_degree`` and the mean
      ``2·num_edges/num_nodes``, dealt to the nodes in a seeded random
      order.
    * Edges: both ends drawn ∝ ``w``, self-loops and repeats dropped, in
      draw order.
    * The first ``L`` of them are kept, ``L`` the least with ``L`` plus
      the nodes they leave without a neighbour equal to ``num_edges``;
      each such node then gets one edge to a node the ``L`` edges cover,
      drawn ∝ ``w``.
    """
    if not num_nodes <= num_edges < num_nodes * (num_nodes - 1) // 2:
        raise ValueError("num_edges must lie in [V, V(V-1)/2)")
    a = 1.0 / (exponent - 1.0)
    i = np.arange(num_nodes, dtype=np.float64)
    target = max_degree * num_nodes / (2.0 * num_edges)

    def top_over_mean(i0):
        w = (i + i0) ** -a
        return w[0] / w.mean()

    lo, hi = np.log(1e-9), np.log(1e9)      # top_over_mean falls with i0
    if top_over_mean(np.exp(lo)) < target:
        raise ValueError(f"no i0 gives max_degree {max_degree} at exponent "
                         f"{exponent}: lower the exponent")
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if top_over_mean(np.exp(mid)) > target \
            else (lo, mid)
    w = (i + np.exp(lo)) ** -a
    rng = np.random.default_rng(np.random.SeedSequence((_GEN_DOMAIN, seed)))
    w = w[rng.permutation(num_nodes)]
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0

    def draw(m, weights_cdf):
        # a sorted sample in a random order is a sample; sorted queries
        # search the large table in cache order
        ids = np.searchsorted(weights_cdf, np.sort(rng.random(m)),
                              side="right")
        return ids[rng.permutation(m)]

    keys = np.zeros(0, dtype=np.int64)
    while True:
        m = num_edges + num_edges // 4
        u, v = draw(m, cdf), draw(m, cdf)
        keep = u != v
        lo_, hi_ = np.minimum(u, v)[keep], np.maximum(u, v)[keep]
        keys = np.concatenate([keys, lo_ * num_nodes + hi_])
        _, first = np.unique(keys, return_index=True)
        if len(first) >= num_edges:
            break
    keys = keys[np.sort(first)]
    src, dst = keys // num_nodes, keys % num_nodes
    # the position at which each node is first covered, and L
    pos = np.full(num_nodes, len(keys), dtype=np.int64)
    for ends in (dst, src):
        nodes, first = np.unique(ends, return_index=True)
        pos[nodes] = np.minimum(pos[nodes], first)
    covered = np.concatenate([[0], np.cumsum(
        np.bincount(pos[pos < len(keys)], minlength=len(keys)))])
    L = int(np.argmax(np.arange(len(keys) + 1) + num_nodes - covered
                      == num_edges))
    src, dst = src[:L], dst[:L]
    alone = np.flatnonzero(pos >= L)
    wc = np.where(pos < L, w, 0.0)
    cdf_c = np.cumsum(wc / wc.sum())
    cdf_c[-1] = 1.0
    partner = draw(len(alone), cdf_c)
    return (np.concatenate([src, alone]).astype(np.int64),
            np.concatenate([dst, partner]).astype(np.int64))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="write a seeded Chung-Lu graph as an edge-list .npz")
    ap.add_argument("--out", required=True)
    ap.add_argument("--nodes", type=int, default=5000)
    ap.add_argument("--edges", type=int, default=13000)
    ap.add_argument("--max-degree", type=float, default=500.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    src, dst = chung_lu_edges(args.seed, args.nodes, args.edges,
                              args.max_degree)
    g = Graph.from_edges(src, dst, args.nodes)
    g.save(args.out)
    print(f"{g.num_nodes} nodes, {g.num_edges} edges, degrees "
          f"{g.degrees.min()}..{g.degrees.max()} → {args.out}")


if __name__ == "__main__":
    main()
