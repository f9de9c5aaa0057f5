"""Plain reference of the graph ingestion path: which (center, context)
ids each worker of a process is fed, from an edge list and a seed.

Semantics (DeepWalk's walks, the paper's RANDOM SAMPLING of them, as
this system defines them for a seed):

* The graph is the simple undirected graph on the edge list: an edge
  listed twice counts once, a self-loop not at all.
* Rows are the nodes in descending order of degree, ties by node id.
  Every worker's rows are all nodes; its noise is degree^0.75,
  normalised.
* Worker ``w``'s walk starts: with ``A`` the rows of degree at least 1,
  in row order, ``round(rate · γ · |A|)`` draws ``s`` from
  ``[0, γ · |A|)`` with replacement, by ``integers(0, γ|A|, size,
  dtype=int64)`` of ``default_rng(SeedSequence((0x6EA1, 0, seed, w)))``;
  start ``s`` is row ``A[s mod |A|]``.
* Block ``b`` of epoch ``e`` is starts ``[b·walks_per_block,
  (b+1)·walks_per_block)``, with one generator
  ``default_rng(SeedSequence((0x6EA1, 1, seed, w, e, b)))``, used in
  this order: the walks, ``t`` rows long, advancing all together, each
  step one call ``integers(0, degree)`` over the block's current rows,
  which picks that neighbour in ascending order; then a window per
  walk token from ``integers(1, window + 1, size=n·t)`` in walk-major
  order; pairs at each offset ``k = 1..window`` within one walk,
  forward (centre at ``i``, context ``i+k``, when ``k`` ≤ the window at
  ``i``) then backward (centre ``i+k``, context ``i``, when ``k`` ≤ the
  window at ``i+k``), each in walk-major order; then one permutation of
  the block's pairs.
* A chunk is the next ``steps · batch`` pairs of the worker's blocks,
  reshaped to ``(steps, batch)``; the stream starts its sample again
  when it runs out.
* Process ``p`` of ``P`` holds workers ``[p·n // P, (p+1)·n // P)``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

_DOMAIN, _START, _WALK = 0x6EA1, 0, 1


@dataclass
class RefGraph:
    offsets: np.ndarray     # (V+1,): row r's neighbours are adj[offsets[r]:offsets[r+1]]
    adj: np.ndarray         # neighbour rows, ascending within each row
    degree: np.ndarray      # (V,) per row

    def noise(self, power: float = 0.75) -> np.ndarray:
        p = self.degree.astype(np.float64) ** power
        return p / p.sum()


def build(src, dst, num_nodes: int) -> RefGraph:
    """The graph in degree-ordered rows, neighbours ascending."""
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    keep = src != dst
    a, b = np.minimum(src, dst)[keep], np.maximum(src, dst)[keep]
    edges = np.unique(np.stack([a, b], axis=1), axis=0)
    degree = (np.bincount(edges[:, 0], minlength=num_nodes)
              + np.bincount(edges[:, 1], minlength=num_nodes))
    order = np.lexsort((np.arange(num_nodes), -degree))
    row = np.empty(num_nodes, dtype=np.int64)
    row[order] = np.arange(num_nodes)
    ends = np.concatenate([row[edges], row[edges][:, ::-1]])
    ends = ends[np.lexsort((ends[:, 1], ends[:, 0]))]
    deg = np.bincount(ends[:, 0], minlength=num_nodes)
    offsets = np.concatenate([[0], np.cumsum(deg)])
    return RefGraph(offsets=offsets, adj=ends[:, 1], degree=deg)


def walk_starts(g: RefGraph, worker: int, rate: float, walks_per_node: int,
                seed: int) -> np.ndarray:
    active = np.flatnonzero(g.degree >= 1)
    space = walks_per_node * len(active)
    target = max(1, int(round(rate * space)))
    rng = np.random.default_rng(np.random.SeedSequence(
        (_DOMAIN, _START, seed, worker)))
    s = rng.integers(0, space, size=target, dtype=np.int64)
    return active[s % len(active)]


def block_pairs(g: RefGraph, starts: np.ndarray, walk_length: int,
                window: int, seed: int, worker: int, epoch: int,
                block: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence(
        (_DOMAIN, _WALK, seed, worker, epoch, block)))
    n, t = len(starts), walk_length
    walks = np.zeros((n, t), dtype=np.int64)
    walks[:, 0] = starts
    for i in range(1, t):
        cur = walks[:, i - 1]
        pick = rng.integers(0, g.degree[cur])
        walks[:, i] = g.adj[g.offsets[cur] + pick]
    win = rng.integers(1, window + 1, size=n * t).reshape(n, t)
    cs, xs = [], []
    for k in range(1, window + 1):
        if k >= t:
            cs += [np.zeros(0, np.int64)] * 2
            xs += [np.zeros(0, np.int64)] * 2
            continue
        left, right = walks[:, :t - k], walks[:, k:]
        fwd = k <= win[:, :t - k]
        bwd = k <= win[:, k:]
        cs += [left[fwd], right[bwd]]
        xs += [right[fwd], left[bwd]]
    c = np.concatenate(cs).astype(np.int32)
    x = np.concatenate(xs).astype(np.int32)
    perm = rng.permutation(len(c))
    return c[perm], x[perm]


def local_workers(workers: int, process_index: int,
                  process_count: int) -> range:
    return range(process_index * workers // process_count,
                 (process_index + 1) * workers // process_count)


def worker_chunks(g: RefGraph, worker: int, *, rate: float,
                  walks_per_node: int, walk_length: int, window: int,
                  walks_per_block: int, chunks: int, steps: int, batch: int,
                  seed: int, epoch: int = 0
                  ) -> tuple[np.ndarray, np.ndarray]:
    """The first ``chunks`` chunks of ``worker``: two ``(chunks, steps,
    batch)`` id arrays."""
    starts = walk_starts(g, worker, rate, walks_per_node, seed)
    n_blocks = -(-len(starts) // walks_per_block)
    need = chunks * steps * batch
    cs, xs, have, b = [], [], 0, 0
    while have < need:
        blk = b % n_blocks
        c, x = block_pairs(
            g, starts[blk * walks_per_block:(blk + 1) * walks_per_block],
            walk_length, window, seed, worker, epoch, blk)
        cs.append(c)
        xs.append(x)
        have += len(c)
        b += 1
    shape = (chunks, steps, batch)
    return (np.concatenate(cs)[:need].reshape(shape),
            np.concatenate(xs)[:need].reshape(shape))
