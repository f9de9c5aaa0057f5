"""Pipelined HBM-blocked fused SGNS step: overlapped DMA, deduped rows.

``sgns_fused_hbm.py`` made the paper's 300k×500 sub-model shape feasible
by keeping the ``(V, d)`` tables HBM-resident and DMA-streaming each
pair block's touched rows — but its memory pipeline is fully serial:
every row gather and every RMW scatter is issued start→wait, one row at
a time, so the compute units idle through all of the step's DMA latency
(the remaining hot-path item on ROADMAP). This module replaces that loop
with a **multi-slot DMA pipeline** in a single ``pallas_call`` per step:

* a ring of ``ring_depth`` VMEM row-buffer pairs (one ``(R_W, d)`` W
  buffer + one ``(R_C, d)`` C buffer per slot) with per-slot DMA
  semaphores, through which block *i+1*'s row gathers are in flight
  while block *i* computes and older blocks' scatters drain; the ring
  defaults to the classic 2 slots (``NUM_SLOTS``) and deepens to any
  ``ring_depth ≥ 2`` — a deeper ring leaves older blocks' write-backs
  in flight longer before their slot-recycling wait;
* **touched-row dedup**: each block gathers every row it touches
  exactly once (the unique centers for W; the unique contexts ∪
  negatives for C), applies all of its updates to the VMEM-resident
  copy, and writes each row back exactly once. This removes the
  per-duplicate gathers *and* the entire read-modify-write round-trip
  of the unpipelined kernel — per-block HBM traffic drops from
  ``3·blk·(K+2)`` row transfers to ``2·R`` where ``R ≤ blk·(K+2)`` is
  the unique-row count;
* a **pure-JAX block planner** (:func:`plan_blocks`) that computes the
  dedup, the pair→buffer-slot index maps, and the scatter-before-
  regather **hazard flags** outside the kernel, and a per-position
  schedule template (:func:`position_template`) that the kernel body
  loops over and :func:`kernel_schedule` expands for the unit tests —
  the schedule (slot assignment, gather/compute/scatter/wait ordering,
  hazard guards) is testable entirely without Pallas.

Hazard ordering: with the chain semantics, block *b*'s gathers must
observe every earlier block's applied updates. Pipelining reorders block
*b*'s gathers before older blocks' scatters have drained, which is only
sound when the row sets are disjoint — so the planner emits
``hazard[b] = touched(b) ∩ (written(b-1) ∪ … ∪ written(b-(S-1))) ≠ ∅``
(per table, over the ``S = ring_depth`` ring), and the schedule issues
block *b*'s gathers on the fast path (overlapped) when the flag is
clear, or after draining every still-outstanding write-back when it is
set. Blocks older than the window are always drained by then: the
S-slot ring reuses block *b-S*'s buffers for block *b*, so the
slot-recycling wait already serializes against everything older — which
is why a window of S-1 look-behind flags is sufficient for full chain
fidelity. Each block's scatter drain is guarded by a *partition* of the
hazard outcomes over its window ("first hazard that fires drains it,
else the slot-recycling default"), so every DMA is started and waited
exactly once under every hazard vector — the ``ring_depth = 2``
schedule degenerates to the original complementary ``pl.when`` pairs.

**Frequency tiers** (engine ``pallas_fused_tiered``,
``kernels/sgns_fused_tiered.py``): vocab ids are frequency-sorted, so
:func:`plan_blocks` can route the ``hot_rows`` hottest rows (ids
``< hot_rows``) out of the DMA pipeline entirely — hot ids are dropped
from the gather/scatter lists and from the hazard row sets (dedup and
hazards are computed over **cold rows only**), and their buffer
positions point at a masked pad slot. The tiered kernel serves hot rows
from a pinned VMEM-resident copy of the table prefix instead; this
module's planner/schedule stay the single source of truth for the cold
path. ``hot_rows = 0`` (the ``pallas_fused_pipe`` engine) is the pure
pipeline.

Bit-equivalence contract (same as the unpipelined engine): identical
results to running :func:`repro.core.sgns.train_step_sparse` once per
pair block on the replayed counter-PRNG negatives. Dedup preserves it
exactly: the reference's scatter-add applies duplicate-row updates
sequentially in pair order, and the in-VMEM ``.at[pos].add`` applies the
same addends to the same base values in the same order before the row is
written back once. The negative draw uses the same replayable counter
PRNG (:func:`repro.kernels.sgns_fused.fused_negative_ids`); the planner
replays it outside the kernel because the dedup needs the ids — the one
deliberate trade against the in-kernel draw: negative *ids* now exist as
planner metadata (O(B·K) int32, KBs) so that negative *rows* (MBs) move
exactly once.

Hardware notes: every DMA is started on a slot semaphore and waited
exactly once, with matched start/wait structure under every hazard
outcome (the guards partition the hazard-outcome space). The same
kernel body compiles under Mosaic and runs in interpret mode (the CPU
tests), which executes the DMAs serially. What Mosaic requires shapes
it: tables in a ``(V, 1, dp)`` row layout (:func:`to_row_layout`),
scalars read from SMEM (the per-block metadata is DMA'd into an SMEM
ring), rows moved one at a time between the DMA ring and the compute
rows, the schedule walked as a loop over positions
(:func:`position_template`), and vmap folded into the kernel's worker
grid (:func:`_worker_kernel`). ``sequential=True`` (word2vec's per-pair
apply order) is inherently unpipelineable and is served by the
unpipelined kernel, in interpret mode only — see
:class:`repro.core.engine.FusedPipePallasEngine`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.sgns import sparse_row_grads_per_pair
from repro.kernels.sgns_fused import _as_seed, fused_negative_ids
from repro.kernels.mode import resolve_interpret
from repro.kernels.sgns_fused_hbm import _pick_block_pairs

NUM_SLOTS = 2   # default ring depth: gathers of b+1 overlap scatters of b

# DMA semantics of the schedule ops: each start op and the wait op that
# retires it, both on the same per-slot semaphore ring. The static
# analysis layer (repro.analysis.dma_model) checks matched start/wait
# structure against exactly this mapping.
DMA_WAIT_FOR_START = {"gather": "wait_gather", "scatter": "wait_scatter"}


# ---------------------------------------------------------------------------
# Block planner — pure JAX, unit-testable without Pallas.
# ---------------------------------------------------------------------------
class PipelinePlan(NamedTuple):
    """Per-block DMA/compute metadata for one step's pair blocks.

    Shapes: ``nblocks`` blocks of ``blk`` pairs (the batch is padded to
    a whole number of blocks; padded pairs carry ``mask == 0`` and
    contribute exactly-zero updates). ``R_W = blk`` and
    ``R_C = blk·(K+1)`` are the row-buffer capacities.

    With a hot tier (``hot_rows > 0``), the unique sets / counts /
    hazards cover **cold rows only** (ids ``≥ hot_rows``); a hot pair
    element's ``*_pos`` entry points at the first pad slot of its
    buffer (its update is tier-masked to zero there — the kernel
    applies it to the VMEM-resident hot prefix instead, indexed
    directly by the id carried in ``cen``/``ctx``/``neg``).
    """

    uw: jax.Array       # (nblocks, R_W) int32 — sorted unique cold center rows, padded with V
    uc: jax.Array       # (nblocks, R_C) int32 — sorted unique cold context∪negative rows, padded with V
    n_w: jax.Array      # (nblocks,) int32 — valid cold rows in uw (gathered AND scattered)
    n_c: jax.Array      # (nblocks,) int32 — valid cold rows in uc
    w_pos: jax.Array    # (nblocks, blk) int32 — pair j's center row → uw slot
    cp_pos: jax.Array   # (nblocks, blk) int32 — pair j's context row → uc slot
    cn_pos: jax.Array   # (nblocks, blk·K) int32 — pair j's k-th negative row → uc slot
    mask: jax.Array     # (nblocks, blk) float32 — 1 for real pairs, 0 for padding
    hazard: jax.Array   # (nblocks,) int32 — 1 iff touched(b) ∩ written(b-1..b-(S-1)) ≠ ∅
    cen: jax.Array      # (nblocks, blk) int32 — blocked center ids (hot-tier direct index)
    ctx: jax.Array      # (nblocks, blk) int32 — blocked context ids
    neg: jax.Array      # (nblocks, blk·K) int32 — blocked negative ids

    @property
    def nblocks(self) -> int:
        return self.uw.shape[0]

    @property
    def block_pairs(self) -> int:
        return self.w_pos.shape[1]


def _pad_to_blocks(x: jax.Array, nblocks: int, blk: int) -> jax.Array:
    """(B, ...) → (nblocks, blk, ...), padding with the first element
    (any valid id — padded pairs are masked to zero-update anyway)."""
    pad = nblocks * blk - x.shape[0]
    if pad:
        x = jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
    return x.reshape((nblocks, blk) + x.shape[1:])


def _unique_rows(ids: jax.Array, vocab_size: int):
    """Per-block sorted unique ids, padded with ``vocab_size``.

    ids: (nblocks, R) int32 in [0, V) ∪ {V} (V marks entries already
    routed elsewhere — the hot tier). Returns (u (nblocks, R), n
    (nblocks,)): ``u[b, :n[b]]`` is block b's sorted unique set of
    ids < V and ``u[b, n[b]:] == V`` (past every real id, so a lookup
    by :func:`_rank_in` of a valid id never lands on padding). Two
    sorts per block and no gather: the second sort moves the first
    occurrences to the front in ascending order, every duplicate and
    sentinel having been overwritten with V.
    """
    V = jnp.int32(vocab_size)
    s = jnp.sort(ids, axis=1)
    first = jnp.concatenate(
        [jnp.ones(s.shape[:1] + (1,), bool), s[:, 1:] != s[:, :-1]], axis=1)
    # sentinel entries (== V) are not counted as unique rows
    keep = first & (s < V)
    n = keep.sum(axis=1).astype(jnp.int32)
    return jnp.sort(jnp.where(keep, s, V), axis=1), n


def _rank_in(u: jax.Array, q: jax.Array) -> jax.Array:
    """Per-block left insertion index of each query in its sorted row:
    ``out[b, i] = #{j : u[b, j] < q[b, i]}``, which is
    ``searchsorted(u[b], q[b], side="left")``. One broadcast compare
    reduced over the row, which XLA fuses without materializing it:
    ``R_u·R_q`` compares per block, where a binary search is a loop of
    dependent gathers that a TPU issues element by element."""
    return jnp.sum(u[:, None, :] < q[:, :, None], axis=-1, dtype=jnp.int32)


def plan_blocks(
    centers: jax.Array,
    contexts: jax.Array,
    negatives: jax.Array,
    vocab_size: int,
    block_pairs: int,
    *,
    hot_rows: int = 0,
    ring_depth: int = NUM_SLOTS,
) -> PipelinePlan:
    """Plan one step's pair blocks for the pipelined kernel.

    Pure JAX (jit/vmap-safe, static shapes): splits the batch into
    ``blk``-pair blocks, routes each touched row to its tier (ids
    ``< hot_rows`` are hot — dropped from the gather/scatter lists and
    the hazard row sets; the rest are cold), dedups each block's
    touched cold rows per table, maps every pair's (center, context,
    negatives) to positions in the deduped row buffers, and flags the
    blocks whose cold touched set intersects any of the previous
    ``ring_depth - 1`` blocks' written sets (the scatter-before-
    regather hazards the schedule must serialize on; a deeper ring
    leaves more write-backs in flight, so the look-behind window grows
    with it).

    No gathers and no loops: every buffer position is a count of the
    smaller rows of its block's sorted unique set (:func:`_rank_in`),
    every hazard a fused equality compare of two blocks' sets, so the
    cost per block is ``R·R`` vector compares with ``R_C = blk·(K+1)``
    — ``blk·(K+1)²`` compares per pair (9,216 at blk 256, K 5).
    """
    B = centers.shape[0]
    K = negatives.shape[1]
    blk = _pick_block_pairs(B, block_pairs)
    nblocks = -(-B // blk)
    V = vocab_size

    cen = _pad_to_blocks(centers.astype(jnp.int32), nblocks, blk)
    ctx = _pad_to_blocks(contexts.astype(jnp.int32), nblocks, blk)
    neg = _pad_to_blocks(negatives.astype(jnp.int32), nblocks, blk)
    negf = neg.reshape(nblocks, blk * K)

    # tier routing: hot ids leave the DMA path entirely — mapped to the
    # V sentinel so they sort past every cold id and out of the counts
    def cold(ids):
        if hot_rows <= 0:
            return ids
        return jnp.where(ids < jnp.int32(hot_rows), jnp.int32(V), ids)

    uw, n_w = _unique_rows(cold(cen), V)
    c_rows = jnp.concatenate([cold(ctx), cold(negf)], axis=1)
    uc, n_c = _unique_rows(c_rows, V)

    # hot elements look up the V sentinel → the first pad slot (clamped
    # to the buffer when a block is entirely cold, in which case no hot
    # lookups exist and the clamp is a no-op)
    w_pos = jnp.minimum(_rank_in(uw, cold(cen)), uw.shape[1] - 1)
    c_pos = jnp.minimum(_rank_in(uc, c_rows), uc.shape[1] - 1)
    cp_pos, cn_pos = c_pos[:, :blk], c_pos[:, blk:]

    # With dedup, written(b) == touched(b) per table (every gathered row
    # receives at least one update), so the look-behind intersections are
    # over the same padded unique sets. W rows only conflict with W
    # writes, C rows with C writes — the tables are separate buffers.
    # The window covers the S-1 blocks whose write-backs a ring of S
    # slots can still have in flight when block b's gathers issue.
    # Membership by one fused compare of the two padded sets (R·R per
    # block pair), the sentinel V excluded on the probing side.
    def hit(u, m):
        found = (u[m:][:, :, None] == u[:-m][:, None, :]).any(axis=-1)
        return (found & (u[m:] < jnp.int32(V))).any(axis=1)

    hz = jnp.zeros((nblocks,), bool)
    for m in range(1, min(ring_depth, nblocks)):
        hz = hz.at[m:].set(hz[m:] | hit(uw, m) | hit(uc, m))

    mask = (jnp.arange(nblocks * blk, dtype=jnp.int32) < B).astype(
        jnp.float32).reshape(nblocks, blk)
    return PipelinePlan(uw=uw, uc=uc, n_w=n_w, n_c=n_c, w_pos=w_pos,
                        cp_pos=cp_pos, cn_pos=cn_pos, mask=mask,
                        hazard=hz.astype(jnp.int32),
                        cen=cen, ctx=ctx, neg=negf)


# ---------------------------------------------------------------------------
# The pipeline schedule — one per-position template, the single source of
# truth for the kernel body (which loops over positions, hazard guards
# becoming pl.when) and for the tests and the DMA model checker (which
# expand it into the static event list and resolve the guards against a
# concrete hazard vector).
# ---------------------------------------------------------------------------
def position_template(num_slots: int = NUM_SLOTS):
    """The events of pipeline position b, relative to b, as ``(op, block
    offset, guard)``; each guard condition is ``(hazard offset, want)``.
    An event exists at b when its block and every hazard it reads lie in
    ``[0, nblocks)``. ``op`` ∈ {gather, wait_gather, compute, scatter,
    wait_scatter}."""
    S = num_slots
    if S < 2:
        raise ValueError(f"ring needs at least 2 slots, got {S}")
    # slot-recycling default drain of block b+1-S, whose buffers block
    # b+1 is about to gather into — fires iff no hazard in its window
    # hazard[b+2-S .. b] drained it earlier
    ev = [("wait_scatter", 1 - S, tuple((f, False) for f in range(2 - S, 1))),
          ("gather", 1, ((1, False),)),
          ("wait_gather", 0, ()), ("compute", 0, ()), ("scatter", 0, ())]
    # hazard path: drain every still-outstanding write-back (oldest
    # first) before issuing block b+1's gathers — block b-t is
    # outstanding here iff no flag in hazard[b-t+1 .. b] fired (which
    # would have drained it already)
    for t in range(S - 2, -1, -1):
        ev.append(("wait_scatter", -t,
                   tuple((f, False) for f in range(1 - t, 1)) + ((1, True),)))
    ev.append(("gather", 1, ((1, True),)))
    return ev


def schedule_tail(nblocks: int, num_slots: int = NUM_SLOTS):
    """Events after the last position: blocks whose slot-recycling
    default lies past it drain on "no later hazard fired" (the
    partition remainder)."""
    S = num_slots
    return [("wait_scatter", j, j % S,
             tuple((f, False) for f in range(j + 1, nblocks)) or None)
            for j in range(max(0, nblocks - S + 1), nblocks)]


def kernel_schedule(nblocks: int, num_slots: int = NUM_SLOTS):
    """The unrolled pipeline as ``(op, block, slot, guard)`` events.

    ``guard`` is ``None`` (unconditional) or a tuple of ``(b, want)``
    conditions meaning "only when bool(hazard[b]) == want for every
    condition". For each block, the guards over its wait_scatter sites
    PARTITION the hazard-outcome space of its look-behind window, so
    every DMA is started and waited exactly once for every hazard
    vector (``num_slots = 2`` degenerates to the original
    complementary single-flag pairs):

    * block b+1's gathers are issued *before* outstanding scatters when
      ``hazard[b+1]`` is clear (the overlap fast path), else after
      every still-in-flight write-back has drained;
    * block j's scatters drain at the FIRST hazard in its window
      ``hazard[j+1 .. j+S-1]`` that fires, or — when none fires — at
      the slot-recycling default (top of position ``j+S-1``, always
      before block ``j+S``'s gathers reuse block j's buffer slot).
    """
    S = num_slots
    template = position_template(S)
    ev = [("gather", 0, 0, None)]
    for b in range(nblocks):
        for op, off, guard in template:
            blk = b + off
            if 0 <= blk < nblocks and all(0 <= b + f < nblocks
                                          for f, _ in guard):
                ev.append((op, blk, blk % S,
                           tuple((b + f, w) for f, w in guard) or None))
    return ev + schedule_tail(nblocks, S)


def resolve_schedule(hazard, num_slots: int = NUM_SLOTS):
    """The concrete ``(op, block, slot)`` event order the kernel executes
    for a given hazard vector — what the planner property tests check."""
    return [(op, b, s)
            for op, b, s, g in kernel_schedule(len(hazard), num_slots)
            if g is None or all(bool(hazard[f]) is w for f, w in g)]


def row_traffic(plan: PipelinePlan, hot_rows: int = 0) -> jax.Array:
    """HBM row transfers one step under this plan actually moves, as an
    int32 device scalar: each valid cold row is exactly one gather plus
    one write-back, and a hot prefix of ``hot_rows`` rows moves in and
    out once per step for both tables (the tiered kernel's
    ``HOT_PREFIX_DMA_OPS`` bulk copies). The step computes it beside its
    loss, for the ``sgns.rows_moved`` counter."""
    return (2 * (jnp.sum(plan.n_w) + jnp.sum(plan.n_c))
            + 4 * hot_rows).astype(jnp.int32)


def hot_touches(centers: jax.Array, contexts: jax.Array,
                negatives: jax.Array, hot_rows: int) -> jax.Array:
    """Row references of one step that fall in the hot tier, as an int32
    device scalar: the centres, contexts and negatives (``K + 2`` per
    pair, padding excluded) with ``id < hot_rows``. For the
    ``sgns.hot_touches`` counter; 0 without a hot tier."""
    if hot_rows <= 0:
        return jnp.int32(0)
    hot = jnp.int32(hot_rows)
    return sum(jnp.sum(a < hot, dtype=jnp.int32)
               for a in (centers, contexts, negatives))


def plan_row_traffic(plan: PipelinePlan, hot_rows: int = 0) -> int:
    """:func:`row_traffic` read back: the ``hbm_rows_per_step`` quantity
    the ``@zipf50k`` BENCH rows gate on and ``repro.analysis.contracts``
    certifies against the committed baseline."""
    return int(row_traffic(plan, hot_rows))


# ---------------------------------------------------------------------------
# Table row layout. Mosaic DMAs a table row only as a leading-dim slice
# whose lane extent is a multiple of 128: a (V, d) f32 table is tiled
# (8, 128), so one row is an unaligned sublane slice, and the paper's
# d = 500 is an unaligned lane extent. The kernels therefore take each
# table as (V, 1, dp), dp the 128-lane round-up of d on the chip and d
# itself in interpret mode (no tiling there, so the CPU results stay
# bit-identical to the reference). Pad lanes start at zero and stay
# zero: every update of a pad lane is a product with a zero pad lane.
# ---------------------------------------------------------------------------
LANES = 128


def row_width(d: int, interpret: bool) -> int:
    """Lane width of one table row in the kernels' HBM layout."""
    return d if interpret else -(-d // LANES) * LANES


def to_row_layout(table: jax.Array, interpret: bool) -> jax.Array:
    """``(..., V, d)`` → ``(..., V, 1, dp)`` (zero pad lanes)."""
    d = table.shape[-1]
    pad = row_width(d, interpret) - d
    if pad:
        table = jnp.pad(table, [(0, 0)] * (table.ndim - 1) + [(0, pad)])
    return table[..., None, :]


def from_row_layout(table: jax.Array, d: int) -> jax.Array:
    """``(..., V, 1, dp)`` → ``(..., V, d)``."""
    return table[..., 0, :d]


# ---------------------------------------------------------------------------
# Per-block int32 metadata. Mosaic reads scalars (DMA row ids, buffer
# positions, loop bounds, hazard flags) only from SMEM, which holds a
# few blocks' worth, not a step's: the metadata stays in HBM and each
# block's row is DMA'd into an SMEM ring slot when its gathers issue.
# ---------------------------------------------------------------------------
def meta_layout(blk: int, K: int, hot: bool) -> tuple[dict, int]:
    """Offsets within one block's metadata row, and its length padded
    to whole 128-lane tiles (the DMA moves whole tiles): the DMA lists
    ``uw | uc``, the buffer positions ``w_pos | cp_pos | cn_pos`` and,
    with a hot tier, the ids ``cen | ctx | neg``."""
    sizes = {"uw": blk, "uc": blk * (K + 1), "w_pos": blk, "cp_pos": blk,
             "cn_pos": blk * K}
    if hot:
        sizes.update(cen=blk, ctx=blk, neg=blk * K)
    off, n = {}, 0
    for name, size in sizes.items():
        off[name] = n
        n += size
    return off, -(-n // LANES) * LANES


def block_meta(plan: PipelinePlan, hot: bool) -> tuple[jax.Array, jax.Array]:
    """``(meta (nblocks, 1, M), counts (3·nblocks,))``: the metadata rows
    in :func:`meta_layout` order (the unit dim gives each row its own
    tile, as for the tables), and ``n_w | n_c | hazard``."""
    parts = [plan.uw, plan.uc, plan.w_pos, plan.cp_pos, plan.cn_pos]
    if hot:
        parts += [plan.cen, plan.ctx, plan.neg]
    meta = jnp.concatenate(parts, axis=1)
    _, M = meta_layout(plan.block_pairs, plan.cn_pos.shape[1]
                       // plan.block_pairs, hot)
    meta = jnp.pad(meta, ((0, 0), (0, M - meta.shape[1])))
    return (meta[:, None, :],
            jnp.concatenate([plan.n_w, plan.n_c, plan.hazard]))


# ---------------------------------------------------------------------------
# The kernel. One grid step per worker; every table row moves by DMA
# between the HBM table and a VMEM ring slot, and between the ring and
# the compute rows by single-row loads and stores (Mosaic has no vector
# gather or scatter on VMEM values). Operands:
#   lr (n,) f32, counts (n·3·nblocks,) i32 SMEM | meta (n, nblocks, 1, M)
#   i32 HBM | W, C (n, V, 1, dp) HBM, aliased to the first two outputs
# outputs: W', C' (HBM) | per-block masked loss sums (n·nblocks,) SMEM
# scratch: metadata ring (S, 1, M) SMEM | bufW (S, R_W, 1,
#          dp), bufC (S, R_C, 1, dp) | compute rows w, cp (blk, dp), cn
#          (blk, K, dp) | gather, scatter DMA semaphore rings (S,) | one
#          metadata DMA semaphore | [hot tier: hotW, hotC (kH, 1, dp),
#          two prefix DMA semaphores]
# ---------------------------------------------------------------------------
def _sync_copy(src, dst, sem):
    dma = pltpu.make_async_copy(src, dst, sem)
    dma.start()
    dma.wait()


def _hbm_pipe_kernel(nblocks, blk, K, num_slots, kH, B,
                     lr_ref, cnt_ref, meta_hbm, _w_in, _c_in,
                     w_hbm, c_hbm, loss_ref,
                     meta_s, buf_w, buf_c, rows_w, rows_cp, rows_cn,
                     gsem, ssem, msem, *hot):
    wk = pl.program_id(0)
    off, _ = meta_layout(blk, K, kH > 0)
    dp = buf_w.shape[-1]
    lr = lr_ref[wk]

    hot_w, hot_c, hsem = hot if kH else (None, None, None)

    def count(i):       # n_w | n_c | hazard of this worker
        return cnt_ref[wk * 3 * nblocks + i]

    if kH:
        # Pin the hot tier: one bulk prefix DMA per table, VMEM-resident
        # for the whole step. Disjoint from every cold row (ids ≥ kH), so
        # it needs no hazard ordering against the cold pipeline.
        pins = [pltpu.make_async_copy(t.at[wk, pl.ds(0, kH)], h, hsem.at[i])
                for i, (t, h) in enumerate(((w_hbm, hot_w), (c_hbm, hot_c)))]
        for dma in pins:
            dma.start()
        for dma in pins:
            dma.wait()

    def meta(s, name, j):
        return meta_s[s, 0, off[name] + j]

    def run_rows(b, s, gather, wait):
        """Matched start/wait loops over block b's valid (cold) rows."""
        if gather and not wait:
            # block b's metadata → ring slot s; it stays until block
            # b+S's gathers, which come after this block's last wait
            _sync_copy(meta_hbm.at[wk, b], meta_s.at[s], msem)
        sem = (gsem if gather else ssem).at[s]

        def rows(table, buf, name):
            def body(j, carry):
                hbm_row = table.at[wk, meta(s, name, j)]
                src, dst = ((hbm_row, buf.at[s, j]) if gather
                            else (buf.at[s, j], hbm_row))
                dma = pltpu.make_async_copy(src, dst, sem)
                dma.wait() if wait else dma.start()
                return carry
            return body

        jax.lax.fori_loop(0, count(b), rows(w_hbm, buf_w, "uw"), 0)
        jax.lax.fori_loop(0, count(nblocks + b), rows(c_hbm, buf_c, "uc"), 0)

    def compute(b, s):
        def at_row(j, id_name, pos_name, hot_table, buf, fn):
            """fn(row ref) on the row pair j's element lives in: the
            pinned hot prefix for a hot id, else its ring-slot row."""
            def cold():
                fn(buf.at[s, pl.ds(meta(s, pos_name, j), 1)])
            if not kH:
                return cold()
            i = meta(s, id_name, j)
            pl.when(i < kH)(lambda: fn(hot_table.at[pl.ds(i, 1)]))
            pl.when(i >= kH)(cold)

        def gather_pair(j, carry):
            def put(dst, idx):
                def fn(row):
                    dst[idx] = row[...].reshape((1,) * (dst.ndim - 1) + (dp,))
                return fn
            one = pl.ds(j, 1)
            at_row(j, "cen", "w_pos", hot_w, buf_w,
                   put(rows_w, (one, slice(None))))
            at_row(j, "ctx", "cp_pos", hot_c, buf_c,
                   put(rows_cp, (one, slice(None))))
            for k in range(K):
                at_row(j * K + k, "neg", "cn_pos", hot_c, buf_c,
                       put(rows_cn, (one, pl.ds(k, 1), slice(None))))
            return carry

        jax.lax.fori_loop(0, blk, gather_pair, 0)
        # the exact expressions of the sparse reference — what the
        # bit-equivalence contract stands on
        loss, d_w, d_cp, d_cn = sparse_row_grads_per_pair(
            rows_w[...], rows_cp[...], rows_cn[...])
        m = (jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
             < B - b * blk).astype(jnp.float32)            # (blk, 1)
        rows_w[...] = -lr * (d_w * m)
        rows_cp[...] = -lr * (d_cp * m)
        rows_cn[...] = -lr * (d_cn * m[:, :, None])
        loss_ref[wk * nblocks + b] = jnp.sum(loss[:, None] * m)

        # Same accumulation order as the reference's scatter-adds (W,
        # then C-context, then C-negatives pair-major): duplicate rows
        # add the same addends to the same base values in the same
        # order, so the single write-back per row is bit-identical.
        def add(src, idx):
            def fn(row):
                row[...] = row[...] + src[idx].reshape(row.shape)
            return fn

        def scatter_w(j, carry):
            at_row(j, "cen", "w_pos", hot_w, buf_w,
                   add(rows_w, (pl.ds(j, 1), slice(None))))
            return carry

        def scatter_cp(j, carry):
            at_row(j, "ctx", "cp_pos", hot_c, buf_c,
                   add(rows_cp, (pl.ds(j, 1), slice(None))))
            return carry

        def scatter_cn(j, carry):
            for k in range(K):
                at_row(j * K + k, "neg", "cn_pos", hot_c, buf_c,
                       add(rows_cn, (pl.ds(j, 1), pl.ds(k, 1), slice(None))))
            return carry

        for body in (scatter_w, scatter_cp, scatter_cn):
            jax.lax.fori_loop(0, blk, body, 0)

    ops = {
        "gather": lambda b, s: run_rows(b, s, gather=True, wait=False),
        "wait_gather": lambda b, s: run_rows(b, s, gather=True, wait=True),
        "compute": compute,
        "scatter": lambda b, s: run_rows(b, s, gather=False, wait=False),
        "wait_scatter": lambda b, s: run_rows(b, s, gather=False, wait=True),
    }

    def guarded(op, blk, conds):
        """ops[op] on block blk under the conjunction of conds."""
        run = functools.partial(ops[op], blk, blk % num_slots)
        if not conds:
            return run()
        pred = conds[0]
        for c in conds[1:]:
            pred = jnp.logical_and(pred, c)
        pl.when(pred)(run)

    def hazard(f, want):
        flag = count(2 * nblocks + jnp.clip(f, 0, nblocks - 1))
        return (flag != 0) if want else (flag == 0)

    def in_range(i):
        return jnp.logical_and(i >= 0, i < nblocks)

    # The schedule as a loop over positions — code size, and so compile
    # time, independent of the block count. Each template event runs
    # under its existence test (block and hazards in range) and its
    # hazard guard, both read from SMEM at run time.
    template = position_template(num_slots)

    def position(b, carry):
        for op, off, guard in template:
            conds = [in_range(b + off)] if off else []
            conds += [in_range(b + f) for f, _ in guard if f]
            conds += [hazard(b + f, want) for f, want in guard]
            guarded(op, b + off, conds)
        return carry

    ops["gather"](0, 0)
    jax.lax.fori_loop(0, nblocks, position, 0)
    for op, blk, _, guard in schedule_tail(nblocks, num_slots):
        guarded(op, blk, [hazard(f, want) for f, want in guard or ()])

    if kH:
        # write the hot tier back after every cold write-back has drained
        # (the schedule's tail waits)
        outs = [pltpu.make_async_copy(h, t.at[wk, pl.ds(0, kH)], hsem.at[i])
                for i, (t, h) in enumerate(((w_hbm, hot_w), (c_hbm, hot_c)))]
        for dma in outs:
            dma.start()
        for dma in outs:
            dma.wait()


def vmem_terms(blk: int, K: int, dp: int, num_slots: int, hot_rows: int
               ) -> dict[str, int]:
    """Bytes of VMEM scratch the kernel allocates, by buffer: the W and C
    row rings, the compute rows (the negatives' K padded to 8 sublanes)
    and the pinned hot prefix of both tables."""
    return {"ring_w": 4 * num_slots * blk * dp,
            "ring_c": 4 * num_slots * blk * (K + 1) * dp,
            "compute_rows": 4 * blk * (2 + -(-K // 8) * 8) * dp,
            "hot_prefix": 4 * 2 * hot_rows * dp}


def _pallas_call(n, V, dp, nblocks, blk, K, num_slots, kH, B, interpret):
    _, M = meta_layout(blk, K, kH > 0)
    S = num_slots
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    scratch = [
        pltpu.SMEM((S, 1, M), jnp.int32),                    # metadata ring
        pltpu.VMEM((S, blk, 1, dp), jnp.float32),            # W row ring
        pltpu.VMEM((S, blk * (K + 1), 1, dp), jnp.float32),  # C row ring
        pltpu.VMEM((blk, dp), jnp.float32),                  # compute rows
        pltpu.VMEM((blk, dp), jnp.float32),
        pltpu.VMEM((blk, K, dp), jnp.float32),
        pltpu.SemaphoreType.DMA((S,)),                       # gathers
        pltpu.SemaphoreType.DMA((S,)),                       # scatters
        pltpu.SemaphoreType.DMA(()),                         # metadata
    ]
    if kH:
        scratch += [pltpu.VMEM((kH, 1, dp), jnp.float32),    # hot W
                    pltpu.VMEM((kH, 1, dp), jnp.float32),    # hot C
                    pltpu.SemaphoreType.DMA((2,))]
    # Mosaic's own intermediates of the compute (a few (blk, K, dp)
    # values) come on top of the scratch
    need = sum(vmem_terms(blk, K, dp, S, kH).values()) + 6 * 4 * blk * 8 * dp
    table = jax.ShapeDtypeStruct((n, V, 1, dp), jnp.float32)
    return pl.pallas_call(
        functools.partial(_hbm_pipe_kernel, nblocks, blk, K, S, kH, B),
        grid=(n,),
        in_specs=[smem, smem, any_, any_, any_],
        out_specs=[any_, any_, smem],
        out_shape=[table, table,
                   jax.ShapeDtypeStruct((n * nblocks,), jnp.float32)],
        # in-place tables: operands 3, 4 alias outputs 0, 1
        input_output_aliases={3: 0, 4: 1},
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(max(need, 32 << 20), 120 << 20))),
        interpret=interpret,
        name=obs.SGNS_KERNEL,
    )


@functools.lru_cache(maxsize=None)
def _worker_kernel(V, dp, nblocks, blk, K, num_slots, kH, B, interpret):
    """The kernel as a function of stacked workers ``(lr (n,), counts, meta,
    W, C)`` with leading worker axis n. Mosaic cannot lower a
    vmapped ``pallas_call`` with HBM operands, so vmap over this
    function folds the mapped axis into the worker axis — the kernel's
    grid — instead of letting Pallas batch it."""
    @jax.custom_batching.custom_vmap
    def stacked(lr, cnt, meta, W, C):
        return tuple(_pallas_call(W.shape[0], V, dp, nblocks, blk, K,
                                  num_slots, kH, B, interpret)(
            lr, cnt.reshape(-1), meta, W, C))

    @stacked.def_vmap
    def _fold(axis_size, in_batched, *args):
        args = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, batched in zip(args, in_batched)]
        outs = stacked(*[a.reshape((-1,) + a.shape[2:]) for a in args])
        return (tuple(o.reshape((axis_size, -1) + o.shape[1:]) for o in outs),
                (True,) * len(outs))

    return stacked


@functools.partial(jax.jit, static_argnames=(
    "negatives", "block_pairs", "ring_depth", "hot_rows", "interpret"))
def fused_pipe_rows_step(
    tables: dict,
    centers: jax.Array,
    contexts: jax.Array,
    table: dict,
    key: jax.Array,
    lr: jax.Array,
    *,
    negatives: int,
    block_pairs: int,
    ring_depth: int,
    hot_rows: int,
    interpret: bool,
) -> tuple[dict, jax.Array, dict]:
    """One step of the pipelined (``hot_rows = 0``) or tiered HBM kernel
    on tables already in the kernels' row layout
    (:func:`to_row_layout`) — the form a scan of steps carries, and the
    trainer keeps across chunks, so that no step converts the layout.
    ``hot_rows`` must lie in ``[0, V]``. Returns the tables, the mean
    loss and the step's counters (``repro.obs.STEP_COUNTERS``): the rows
    the kernel moves (:func:`row_traffic`) and the references to hot
    rows (:func:`hot_touches`)."""
    V, _, dp = tables["W"].shape
    B = centers.shape[0]
    K = negatives
    with jax.named_scope(obs.PLAN_SCOPE):
        neg_ids = fused_negative_ids(_as_seed(key), table["prob"],
                                     table["alias"], (B, K))
        plan = plan_blocks(centers, contexts, neg_ids, V, block_pairs,
                           hot_rows=hot_rows, ring_depth=ring_depth)
        meta, cnt = block_meta(plan, hot_rows > 0)
        counts = {obs.ROWS_MOVED: row_traffic(plan, hot_rows),
                  obs.HOT_TOUCHES: hot_touches(centers, contexts, neg_ids,
                                               hot_rows)}
    run = _worker_kernel(V, dp, plan.nblocks, plan.block_pairs, K,
                         ring_depth, hot_rows, B, interpret)
    W, C, loss = run(jnp.reshape(lr, (1,)).astype(jnp.float32), cnt[None],
                     meta[None], tables["W"][None], tables["C"][None])
    # padded pairs were masked to exactly-zero loss, so the batch mean
    # divides by the true pair count
    return {"W": W[0], "C": C[0]}, jnp.sum(loss) / B, counts


@functools.partial(jax.jit, static_argnames=(
    "negatives", "block_pairs", "ring_depth", "hot_rows", "interpret"))
def fused_pipe_step(params, centers, contexts, table, key, lr, *,
                    negatives, block_pairs, ring_depth, hot_rows,
                    interpret=None):
    """:func:`fused_pipe_rows_step` on ``(V, d)`` tables: converts to the
    row layout and back around the one step."""
    interpret = resolve_interpret(interpret)
    d = params["W"].shape[1]
    rows = {k: to_row_layout(v, interpret) for k, v in params.items()}
    rows, loss, _ = fused_pipe_rows_step(
        rows, centers, contexts, table, key, lr, negatives=negatives,
        block_pairs=block_pairs, ring_depth=ring_depth, hot_rows=hot_rows,
        interpret=interpret)
    return {k: from_row_layout(v, d) for k, v in rows.items()}, loss


def sgns_fused_pipe_step(
    params: dict,
    centers: jax.Array,
    contexts: jax.Array,
    table: dict,
    key: jax.Array,
    lr: jax.Array,
    *,
    negatives: int = 5,
    block_pairs: int = 256,
    ring_depth: int = NUM_SLOTS,
    interpret: bool | None = None,
) -> tuple[dict, jax.Array]:
    """One SGNS step through the pipelined HBM engine.

    Same contract as :func:`repro.kernels.sgns_fused_hbm.sgns_fused_hbm_step`
    with ``sequential=False`` — and bit-identical to it in interpret mode
    (and therefore to the per-block ``train_step_sparse`` reference on
    the replayed negatives) at every ``ring_depth``: the planner replays
    the same counter-PRNG draw, and the schedule's hazard guards
    preserve the chain's read-after-write semantics exactly. One
    ``pallas_call`` covers the whole batch. ``interpret=None`` compiles
    with Mosaic on a TPU and interprets elsewhere.
    """
    return fused_pipe_step(params, centers, contexts, table, key, lr,
                           negatives=negatives, block_pairs=block_pairs,
                           ring_depth=ring_depth, hot_rows=0,
                           interpret=interpret)
