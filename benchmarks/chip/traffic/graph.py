"""Graph traffic: a simple graph with a power-law degree law, drawn
from the seed.

A copy of ``repro.data.graph.chung_lu_edges``, so that the benchmark's
inputs do not change when the program's copy does. Chung–Lu with
power-law expected degrees ``w_i ∝ (i + i0)^(-1/(exponent-1))``, dealt
to the nodes in a seeded random order; exactly ``num_edges`` edges, no
self-loop or repeat, and every node with a neighbour.
"""

from __future__ import annotations

import numpy as np

_GEN_DOMAIN = 0x6EA2


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
