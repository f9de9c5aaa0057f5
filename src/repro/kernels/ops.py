"""jit'd wrappers around the Pallas kernels — padding, gather/scatter.

``sgns_row_grads`` is a drop-in for
:func:`repro.core.sgns.sparse_row_grads`; the ``pallas`` update engine
(``repro.core.engine``) routes the sparse step's row gradients through
it. ``interpret=None`` compiles the kernel with Mosaic on a TPU and runs
it in interpret mode elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mode import resolve_interpret
from repro.kernels.sgns_update import sgns_row_grads_kernel, _pick_block_b
from repro.kernels import ref


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def sgns_row_grads(
    w: jax.Array,
    c_pos: jax.Array,
    c_neg: jax.Array,
    *,
    interpret: bool | None = None,
    block_b: int | None = None,
):
    """Kernel-backed row grads with automatic lane/batch padding.

    Returns (mean_loss, dW (B,D), dC_pos (B,D), dC_neg (B,K,D)) — the
    same contract as ``sgns.sparse_row_grads`` (sum-loss gradients,
    mean loss for reporting).
    """
    B, D = w.shape
    K = c_neg.shape[1]
    Dp = _round_up(D, 128)
    # The wrapper pads B up to a block multiple, so ask the picker for a
    # block sized to the next pow2 ≥ B (divisibility comes from padding,
    # not from shrinking the block).
    bt = block_b or _pick_block_b(1 << (max(B, 8) - 1).bit_length(), K, Dp)
    Bp = _round_up(max(B, bt), bt)

    pad2 = lambda a: jnp.pad(a, ((0, Bp - B), (0, Dp - D)))
    pad3 = lambda a: jnp.pad(a, ((0, Bp - B), (0, 0), (0, Dp - D)))
    loss, dw, dcp, dcn = sgns_row_grads_kernel(
        pad2(w), pad2(c_pos), pad3(c_neg), block_b=bt,
        interpret=resolve_interpret(interpret))
    mean_loss = jnp.sum(loss[:B]) / B
    return mean_loss, dw[:B, :D], dcp[:B, :D], dcn[:B, :, :D]


def make_row_grad_fn(interpret: bool | None = None,
                     block_b: int | None = None):
    """row_grad_fn for AsyncShardTrainer / train_step_sparse."""

    def fn(w, c_pos, c_neg):
        return sgns_row_grads(w, c_pos, c_neg, interpret=interpret,
                              block_b=block_b)

    return fn


@functools.partial(jax.jit, static_argnames=("interpret",))
def sgns_apply_step(
    params: dict,
    centers: jax.Array,
    contexts: jax.Array,
    negatives: jax.Array,
    lr: jax.Array,
    interpret: bool | None = None,
):
    """Full fused step: gather → kernel → scatter-add (the production path)."""
    w = params["W"][centers]
    c_pos = params["C"][contexts]
    c_neg = params["C"][negatives]
    loss, d_w, d_cp, d_cn = sgns_row_grads(w, c_pos, c_neg, interpret=interpret)
    W = params["W"].at[centers].add(-lr * d_w)
    C = params["C"].at[contexts].add(-lr * d_cp)
    C = C.at[negatives.reshape(-1)].add(-lr * d_cn.reshape(-1, d_cn.shape[-1]))
    return {"W": W, "C": C}, loss


# Re-export oracles so tests can ask one module for both sides.
sgns_row_grads_ref = ref.sgns_row_grads_ref
