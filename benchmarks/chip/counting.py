"""Operations and bytes the algorithms need, counted from their shapes
and ids — never from an implementation's DMA plan, so that a kernel
which moves fewer rows cannot change its own yardstick.

SGNS (one pair, K negatives, width d): the forward takes K+1 dot
products (2·(K+1)·d), the backward two rank-one products per row
(4·(K+1)·d), so 6·(K+1)·d operations per pair.

Bytes: a chunk of S steps touches each distinct input row (W, centers)
and each distinct output row (C, contexts plus the negatives drawn)
at least once, so with the tables in HBM no implementation moves fewer
than one read and one write of ``d`` float32 per distinct row per
chunk. The negatives are random: their distinct count is taken in
expectation under the worker's noise distribution.
"""

from __future__ import annotations

import numpy as np

F32 = 4


def sgns_flops_per_pair(d: int, negatives: int) -> int:
    return 6 * (negatives + 1) * d


def expected_distinct(q: np.ndarray, draws: int,
                      exclude: np.ndarray | None = None) -> float:
    """Expected number of distinct ids among ``draws`` independent draws
    from the distribution ``q``, counting only ids where ``exclude`` is
    False: ``Σ_v 1 - (1 - q_v)^draws``."""
    q = np.asarray(q, dtype=np.float64)
    hit = -np.expm1(draws * np.log1p(-np.minimum(q, 1.0 - 1e-16)))
    hit = np.where(q >= 1.0, 1.0, hit)
    if exclude is not None:
        hit = np.where(exclude, 0.0, hit)
    return float(hit.sum())


def sgns_chunk_rows(centers: np.ndarray, contexts: np.ndarray,
                    q: np.ndarray, negatives: int) -> tuple[int, float]:
    """Distinct W rows and expected distinct C rows one worker's chunk
    touches: ``(|centers|, |contexts ∪ negatives|)``, the second in
    expectation over the ``negatives · len(contexts)`` noise draws."""
    cen = np.unique(np.asarray(centers).ravel())
    ctx = np.unique(np.asarray(contexts).ravel())
    seen = np.zeros(len(q), dtype=bool)
    seen[ctx] = True
    draws = negatives * np.asarray(contexts).size
    return len(cen), len(ctx) + expected_distinct(q, draws, exclude=seen)


def sgns_chunk_bytes(centers, contexts, q, negatives: int, d: int) -> float:
    """The byte floor of one worker's chunk: one read and one write of
    each distinct row, ``d`` float32 wide (not the padded row)."""
    w_rows, c_rows = sgns_chunk_rows(centers, contexts, q, negatives)
    return 2.0 * (w_rows + c_rows) * d * F32


def min_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_memory >= t_compute:
        return t_memory, "hbm_bytes"
    return t_compute, "flops"
