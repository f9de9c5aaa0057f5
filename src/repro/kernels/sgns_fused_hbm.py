"""HBM-blocked fully-fused Pallas SGNS step: the paper-scale variant.

The VMEM-resident fused kernel (``sgns_fused.py``) rides both ``(V, d)``
parameter tables through the kernel whole, which caps it at
VMEM-adjacent table sizes — far short of the paper's 300k×500
sub-models. This variant keeps the tables in **HBM**
(``memory_space=ANY``) and walks the batch in fixed-size *pair blocks*:
one kernel invocation per block, which DMAs (``pltpu.make_async_copy``)
only the rows that block actually touches into VMEM scratch — the
center row, positive-context row and K negative rows of each pair —
and RMW-scatters the updates back. Per-block HBM traffic is
O(block·(K+2)·d) rows instead of O(V·d) tables: the cache-blocking idea
of Ji et al.'s shared-memory word2vec, applied to the TPU memory
hierarchy. The tables are input/output-aliased through every block
invocation, so the whole step is a chain of in-place kernels over one
pair of HBM buffers.

Why a chain of invocations rather than a ``pallas_call`` grid or an
in-kernel block loop: all data that matters moves by explicit DMA (the
blocked operands would be KB-sized id vectors), so a grid buys no
pipelining here — and under interpret mode both a grid and an outer
in-kernel loop demote the HBM refs to loop-carried values whose
per-DMA updates XLA materializes as full-table copies (~GB per step at
paper scale). Single-level in-kernel loops keep every row DMA a true
in-place row update; the chain keeps block b+1 reading block b's
writes. The single-launch double-buffered successor of this chain is
``sgns_fused_pipe.py`` (engine ``pallas_fused_pipe``), which overlaps
block *i+1*'s gathers with block *i*'s compute and block *i-1*'s
scatter drain behind a hazard-ordering block planner.

The negative draw stays inside the kernel (Ordentlich et al.'s
network-efficient property: negative ids never exist off-chip): the
``{prob, alias}`` Vose tables are VMEM-resident operands — ``(V,)``
each, tiny next to the ``(V, d)`` tables — and each block draws its K
negatives per pair with the same replayable counter PRNG as the
VMEM-resident kernel, at counter offsets equal to the pairs' global
row-major draw positions. :func:`repro.kernels.sgns_fused.fused_negative_ids`
on the full ``(B, K)`` shape therefore replays a whole step's draws
bit-exactly, blocked or not, so the existing equivalence tests extend
directly.

Semantics:

* default (``sequential=False``) — within each block, all row gradients
  are computed from the tables as of block start, then applied with
  sequentially-accumulating read-modify-write scatters (duplicate ids
  add up, in update order). Block b+1 reads block b's updates. This is
  *bit-identical* to running :func:`repro.core.sgns.train_step_sparse`
  once per block on the replayed negatives; with one block it is
  bit-identical to a single sparse step over the whole batch.
* ``sequential=True`` — word2vec's true per-pair semantics: each pair's
  gradients are computed from the tables as updated by every earlier
  pair, and applied immediately. Equivalent to a loop of batch-size-1
  sparse steps (to the last ulp: XLA's FMA-contraction choices can
  differ between the two compilations). Inherently serial —
  O(B·(K+2)) chained DMAs — so it is the small-shape fidelity oracle
  for Hogwild-style update-order studies, not a throughput path.

The row gradients use the exact expressions of
:func:`repro.core.sgns.sparse_row_grads`, so the default mode's
"bit-identical" above holds at the float level in interpret mode, not
just to tolerance.

Hardware notes: this kernel keeps the *unpipelined* start→wait-per-row
DMA discipline — the simplest possible oracle for the pipelined
engine's bit-equivalence tests. It runs in interpret mode only: Mosaic
cannot lower its in-kernel draw (alias-table gathers by a vector of
ids), its DMA row ids (vector values, not SMEM scalars) or its
single-row slices of ``(8, 128)``-tiled tables (``NO_MOSAIC``). The
DMA-overlap optimization it deliberately leaves on the table lives in ``sgns_fused_pipe.py``: a ring of VMEM row buffers with
per-slot semaphores, touched-row dedup (one DMA per unique row per
block instead of per-pair RMW round-trips), and planner-computed
scatter-before-regather hazard ordering. This kernel remains the
``sequential=True`` path (word2vec's per-pair apply order is inherently
serial) and the fallback reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sgns import sparse_row_grads_per_pair
from repro.kernels.mode import require_interpret
from repro.kernels.sgns_fused import _as_seed, alias_draw_from_counters

# Why this kernel has no Mosaic lowering (the pipelined kernel in
# sgns_fused_pipe.py is the one that compiles).
NO_MOSAIC = ("its in-kernel draw gathers alias-table entries by a vector "
              "of ids, its DMA row ids are vector values rather than SMEM "
              "scalars, and it slices single rows of (8, 128)-tiled (V, d) "
              "tables")


def _pick_block_pairs(B: int, block_pairs: int) -> int:
    """The main block size: ``block_pairs`` clamped to the batch. A
    batch that is not a multiple gets one shorter *tail* invocation for
    the remainder — never a degradation to tiny blocks (a prime B with
    a divisor-only rule would chain B single-pair kernels)."""
    return max(1, min(int(block_pairs), B))


def _block_negative_ids(seed, prob, alias, pair0, blk: int, K: int):
    """The in-kernel draw for one pair block.

    Counters are the pairs' *global* row-major draw positions (two per
    draw), so the concatenation over blocks equals
    ``fused_negative_ids(seed, prob, alias, (B, K))`` bit-exactly.
    """
    row = jax.lax.broadcasted_iota(jnp.uint32, (blk, K), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (blk, K), 1)
    base = (pair0.astype(jnp.uint32) + row) * jnp.uint32(K) + col
    return alias_draw_from_counters(seed, prob, alias, base)


# ---------------------------------------------------------------------------
# Kernel bodies. Operand order:
#   seed (2,) u32 SMEM | lr (1,) f32 SMEM | pair0 (1,) i32 SMEM
#   cen (blk,) | ctx (blk,) | prob (V,) | alias (V,)          [VMEM]
#   W, C  (V, d) HBM (ANY), aliased to the first two outputs
# outputs: W', C' (ANY) | per-pair loss (blk,) VMEM
# scratch: w_rows | cp_rows | cn_rows | tmp (d,) | one DMA semaphore
# All in-kernel loops are single-level with the K copies unrolled
# (K is static) — see the module docstring for why that matters.
# ---------------------------------------------------------------------------
def _copy(src, dst, sem):
    dma = pltpu.make_async_copy(src, dst, sem)
    dma.start()
    dma.wait()


def _hbm_block_kernel(K, seed_ref, lr_ref, pair0_ref, cen_ref, ctx_ref,
                      prob_ref, alias_ref, _w_in, _c_in,
                      w_hbm, c_hbm, loss_ref,
                      w_rows, cp_rows, cn_rows, tmp, sem):
    blk = cen_ref.shape[0]
    d = tmp.shape[0]
    lr = lr_ref[0]
    ids = _block_negative_ids(seed_ref[...], prob_ref[...], alias_ref[...],
                              pair0_ref[0], blk, K)

    # Gather: DMA only the touched rows of the HBM-resident tables,
    # through the *output* refs (aliased) so this block sees the
    # previous block's applied updates.
    def gather(j, _):
        _copy(w_hbm.at[cen_ref[j]], w_rows.at[j], sem)
        _copy(c_hbm.at[ctx_ref[j]], cp_rows.at[j], sem)
        for k in range(K):
            _copy(c_hbm.at[ids[j, k]], cn_rows.at[j * K + k], sem)
        return 0

    jax.lax.fori_loop(0, blk, gather, 0)

    # the exact expressions of the sparse reference — what the
    # bit-equivalence contract stands on
    loss, d_w, d_cp, d_cn = sparse_row_grads_per_pair(
        w_rows[...], cp_rows[...], cn_rows[...].reshape(blk, K, d))
    u_w = -lr * d_w
    u_cp = -lr * d_cp
    u_cn = (-lr * d_cn).reshape(blk * K, d)
    loss_ref[...] = loss

    # Scatter: sequential read-modify-write per touched row, in the same
    # update order as the sparse reference's three scatter-adds —
    # duplicates accumulate identically.
    def rmw(dst, upd):
        _copy(dst, tmp, sem)
        tmp[...] = tmp[...] + upd
        _copy(tmp, dst, sem)

    def apply_w(j, _):
        rmw(w_hbm.at[cen_ref[j]], u_w[j])
        return 0

    def apply_cp(j, _):
        rmw(c_hbm.at[ctx_ref[j]], u_cp[j])
        return 0

    def apply_cn(j, _):
        for k in range(K):
            rmw(c_hbm.at[ids[j, k]], u_cn[j * K + k])
        return 0

    jax.lax.fori_loop(0, blk, apply_w, 0)
    jax.lax.fori_loop(0, blk, apply_cp, 0)
    jax.lax.fori_loop(0, blk, apply_cn, 0)


def _hbm_sequential_kernel(K, seed_ref, lr_ref, pair0_ref, cen_ref, ctx_ref,
                           prob_ref, alias_ref, _w_in, _c_in,
                           w_hbm, c_hbm, loss_ref,
                           w_rows, cp_rows, cn_rows, tmp, sem):
    """word2vec's per-pair sequential apply: pair j's grads see every
    earlier pair's updates. One invocation covers its whole pair range;
    the scratch holds a single pair's rows."""
    n = cen_ref.shape[0]
    d = tmp.shape[0]
    lr = lr_ref[0]
    seed = seed_ref[...]
    prob = prob_ref[...]
    alias = alias_ref[...]
    pair0 = pair0_ref[0]

    def pair(j, _):
        ids = _block_negative_ids(seed, prob, alias, pair0 + j, 1, K)
        _copy(w_hbm.at[cen_ref[j]], w_rows.at[0], sem)
        _copy(c_hbm.at[ctx_ref[j]], cp_rows.at[0], sem)
        for k in range(K):
            _copy(c_hbm.at[ids[0, k]], cn_rows.at[k], sem)
        w = w_rows[0:1]
        cp = cp_rows[0:1]
        cn = cn_rows[0:K].reshape(1, K, d)
        loss, d_w, d_cp, d_cn = sparse_row_grads_per_pair(w, cp, cn)
        loss_ref[j] = loss[0]
        # batch-1 sparse step: the W/ctx rows were just read, so add-
        # and-write; the K negative rows re-read (the ctx write, or an
        # earlier duplicate negative, may have touched them).
        w_rows[0:1] = w + (-lr * d_w)
        _copy(w_rows.at[0], w_hbm.at[cen_ref[j]], sem)
        cp_rows[0:1] = cp + (-lr * d_cp)
        _copy(cp_rows.at[0], c_hbm.at[ctx_ref[j]], sem)
        u_cn = (-lr * d_cn).reshape(K, d)
        for k in range(K):
            _copy(c_hbm.at[ids[0, k]], tmp, sem)
            tmp[...] = tmp[...] + u_cn[k]
            _copy(tmp, c_hbm.at[ids[0, k]], sem)
        return 0

    jax.lax.fori_loop(0, n, pair, 0)


# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=(
    "negatives", "block_pairs", "sequential", "interpret"))
def sgns_fused_hbm_step(
    params: dict,
    centers: jax.Array,
    contexts: jax.Array,
    table: dict,
    key: jax.Array,
    lr: jax.Array,
    *,
    negatives: int = 5,
    block_pairs: int = 256,
    sequential: bool = False,
    interpret: bool | None = None,
) -> tuple[dict, jax.Array]:
    """One SGNS step with HBM-resident parameter tables.

    Same contract as :func:`repro.kernels.sgns_fused.sgns_fused_step`
    (``params {"W","C"} (V,d)``, ``centers/contexts (B,)``, Vose
    ``table {"prob","alias"}``, ``(2,)`` uint32 key) — but the ``(V, d)``
    tables never enter VMEM whole: the step chains one aliased kernel
    invocation per ``block_pairs``-sized pair block (plus a shorter
    tail invocation when B is not a multiple), each DMA-gathering /
    RMW-scattering only its own block's touched rows.
    ``sequential=True`` applies word2vec's per-pair update order inside
    each block invocation. Interpret mode only (see ``NO_MOSAIC``).
    """
    interpret = require_interpret(interpret, "sgns_fused_hbm_step",
                                  NO_MOSAIC)
    V, d = params["W"].shape
    B = centers.shape[0]
    K = negatives
    blk = _pick_block_pairs(B, block_pairs)
    body = _hbm_sequential_kernel if sequential else _hbm_block_kernel

    def make_call(n: int):
        """A pallas_call processing one ``n``-pair block (the main block
        size, plus one shorter variant when B % blk != 0)."""
        scratch_rows = 1 if sequential else n
        smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
        return pl.pallas_call(
            functools.partial(body, K),
            in_specs=[
                smem(),                                 # seed (2,)
                smem(),                                 # lr (1,)
                smem(),                                 # pair0 (1,)
                pl.BlockSpec(memory_space=pltpu.VMEM),  # centers block
                pl.BlockSpec(memory_space=pltpu.VMEM),  # contexts block
                pl.BlockSpec(memory_space=pltpu.VMEM),  # prob
                pl.BlockSpec(memory_space=pltpu.VMEM),  # alias
                pl.BlockSpec(memory_space=pl.ANY),   # W (HBM)
                pl.BlockSpec(memory_space=pl.ANY),   # C (HBM)
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((V, d), params["W"].dtype),
                jax.ShapeDtypeStruct((V, d), params["C"].dtype),
                jax.ShapeDtypeStruct((n,), jnp.float32),
            ],
            # in-place tables: HBM operands 7, 8 alias outputs 0, 1 —
            # the chain threads one pair of buffers through every block
            input_output_aliases={7: 0, 8: 1},
            scratch_shapes=[
                pltpu.VMEM((scratch_rows, d), jnp.float32),      # centers
                pltpu.VMEM((scratch_rows, d), jnp.float32),      # pos-ctx
                pltpu.VMEM((scratch_rows * K, d), jnp.float32),  # negatives
                pltpu.VMEM((d,), jnp.float32),                   # RMW stage
                pltpu.SemaphoreType.DMA,
            ],
            interpret=interpret,
        )

    calls = {blk: make_call(blk)}
    if B % blk:
        calls[B % blk] = make_call(B % blk)
    seed = _as_seed(key)
    lr1 = jnp.reshape(lr, (1,)).astype(jnp.float32)
    W, C = params["W"], params["C"]
    losses = []
    for b0 in range(0, B, blk):
        n = min(blk, B - b0)
        W, C, loss_b = calls[n](
            seed, lr1, jnp.full((1,), b0, jnp.int32),
            centers[b0:b0 + n], contexts[b0:b0 + n],
            table["prob"], table["alias"], W, C)
        losses.append(loss_b)
    return {"W": W, "C": C}, jnp.mean(jnp.concatenate(losses))
