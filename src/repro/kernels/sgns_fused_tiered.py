"""Frequency-tiered hot/cold fused SGNS step: VMEM-pinned hot rows over
the pipelined HBM engine.

Word frequencies are Zipfian, so a small *hot set* of rows absorbs the
large majority of the per-block DMA traffic the all-HBM pipeline
(``sgns_fused_pipe.py``) pays: at word2vec's unigram^0.75 noise
distribution plus the Zipfian center/context stream, the few hundred
most frequent ids appear in nearly every pair block, yet the pipeline
re-gathers and re-scatters them for every block that touches them.
Ordentlich et al. (1606.08495) built their network-efficient
distributed word2vec on exactly this skew; the paper's input-space-
partitioned async design keeps per-worker tables private, so a
per-worker hot tier needs no cross-worker synchronization of any kind.

This engine (``pallas_fused_tiered``) splits each ``(V, d)`` table at a
build-time-known row index ``hot_rows``:

* **hot tier** — ids ``< hot_rows``. Vocab ids are frequency-sorted
  descending (``data/vocab.build_vocab``), so the hottest rows by
  unigram count are exactly the id prefix, and a row's id doubles as
  its direct index into a VMEM-resident copy of the table prefix. The
  kernel bulk-DMAs the prefix into VMEM scratch once at step start,
  applies every hot update in place through all blocks (chain semantics
  are automatic: computes execute in block order), and writes the
  prefix back once at step end — hot rows move over DMA **once per
  step** instead of once per touching block.
* **cold tier** — ids ``≥ hot_rows``. Exactly the existing pipelined
  path: the :func:`repro.kernels.sgns_fused_pipe.plan_blocks` planner
  (this module's single source of truth for the cold side) dedups,
  position-maps and hazard-flags over cold rows only, and the same
  schedule (:func:`~repro.kernels.sgns_fused_pipe.position_template`)
  drives the ``ring_depth``-slot DMA ring.

The result is a tunable dial on the VMEM-vs-HBM cliff:
``hot_rows = 0`` is the pure pipeline (the ``pallas_fused_pipe``
engine), ``hot_rows = V`` is pure-resident (every row served from VMEM
like ``pallas_fused``, zero per-block row DMAs), and intermediate
settings trade VMEM budget (``2·hot_rows·d`` floats) for DMA traffic
under the corpus's actual skew — ``benchmarks/bench_kernel.py
--hot-sweep`` measures the curve.

Bit-equivalence contract: identical (interpret mode) to
``sgns_fused_hbm`` / ``sgns_fused_pipe`` — and therefore to the
per-block sparse reference on the replayed counter-PRNG negatives — at
**every** hot fraction. Tier routing preserves it exactly: each row id
belongs to exactly one tier, so each physical row receives exactly the
reference's update sequence through exactly one path: each pair
element's row loads and adds go to its tier's copy alone (a scalar branch on
``id < hot_rows`` per pair element), so the compute sees bit-identical
inputs either way and duplicates accumulate in the reference's order.

The kernel itself is the pipelined one
(:func:`repro.kernels.sgns_fused_pipe.fused_pipe_rows_step`) with a
non-zero hot tier; this module holds the tiered entry point.
"""

from __future__ import annotations

from repro.kernels.sgns_fused_pipe import NUM_SLOTS, fused_pipe_step

# Bulk hot-prefix DMAs per step beyond the cold pipeline's schedule: two
# prefix loads at step start (W, C) + two write-backs at step end — the
# ``4 * hot_rows`` row term in
# :func:`repro.kernels.sgns_fused_pipe.plan_row_traffic`.
HOT_PREFIX_DMA_OPS = 4


def sgns_fused_tiered_step(
    params: dict,
    centers,
    contexts,
    table: dict,
    key,
    lr,
    *,
    negatives: int = 5,
    block_pairs: int = 256,
    hot_rows: int = 256,
    ring_depth: int = NUM_SLOTS,
    interpret: bool | None = None,
):
    """One SGNS step through the frequency-tiered hot/cold engine.

    Same contract as
    :func:`repro.kernels.sgns_fused_pipe.sgns_fused_pipe_step` — and
    bit-identical to it in interpret mode (and to ``sgns_fused_hbm_step``
    / the per-block sparse reference on the replayed negatives) at every
    ``hot_rows`` setting. ``hot_rows`` is clamped to ``[0, V]``: 0 is
    the pure pipeline, ``V`` is pure-VMEM-resident (zero per-block row
    DMAs). One ``pallas_call`` covers the whole batch.
    """
    V = params["W"].shape[0]
    return fused_pipe_step(params, centers, contexts, table, key, lr,
                           negatives=negatives, block_pairs=block_pairs,
                           ring_depth=ring_depth,
                           hot_rows=max(0, min(int(hot_rows), V)),
                           interpret=interpret)
