"""SGNS (skip-gram with negative sampling) — the paper's base model.

Pure-JAX reference implementation of word2vec's SGNS objective
(Eq. 1 of the paper):

    log σ(w·c) + Σ_{k} E_{c'~P_D^{3/4}} log σ(−w·c')

Two step functions with identical math:

* ``train_step_dense``   — autodiff through the gathers; materializes a
  dense (V, d) gradient. Simple; used as the oracle in tests.
* ``train_step_sparse``  — manual per-row gradients + scatter-add; the
  production path (O(B·K·d) instead of O(V·d) memory traffic). The
  Pallas kernel in ``repro.kernels`` fuses the middle of this path.

These are the primitives behind the update engines in
:mod:`repro.core.engine` (``dense`` / ``sparse`` / ``pallas`` /
``pallas_fused_tiered``) — trainers select an engine rather than calling these
directly.

Initialization matches word2vec: W ~ U(−0.5/d, 0.5/d), C = 0.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SGNSConfig:
    vocab_size: int
    dim: int = 500            # paper: 500 dims
    window: int = 10          # paper: 10 each side
    negatives: int = 5        # word2vec default k
    lr: float = 0.025         # word2vec default initial alpha
    lr_min: float = 1e-4
    seed: int = 0


def init_params(key: jax.Array, cfg: SGNSConfig) -> dict:
    kw, _ = jax.random.split(key)
    w = jax.random.uniform(
        kw, (cfg.vocab_size, cfg.dim), minval=-0.5 / cfg.dim, maxval=0.5 / cfg.dim,
        dtype=jnp.float32,
    )
    c = jnp.zeros((cfg.vocab_size, cfg.dim), dtype=jnp.float32)
    return {"W": w, "C": c}


def negative_logits_loss(
    w: jax.Array, c_pos: jax.Array, c_neg: jax.Array
) -> jax.Array:
    """Mean SGNS loss for gathered rows w (B,d), c_pos (B,d), c_neg (B,K,d)."""
    s_pos = jnp.sum(w * c_pos, axis=-1)
    s_neg = jnp.einsum("bd,bkd->bk", w, c_neg)
    loss = -jax.nn.log_sigmoid(s_pos) - jnp.sum(jax.nn.log_sigmoid(-s_neg), axis=-1)
    return jnp.mean(loss)


def loss_fn(
    params: dict, centers: jax.Array, contexts: jax.Array, negatives: jax.Array
) -> jax.Array:
    w = params["W"][centers]
    c_pos = params["C"][contexts]
    c_neg = params["C"][negatives]
    return negative_logits_loss(w, c_pos, c_neg)


def sum_loss_fn(
    params: dict, centers: jax.Array, contexts: jax.Array, negatives: jax.Array
) -> jax.Array:
    """Sum-over-pairs loss — word2vec's update semantics: each (w, c)
    pair applies its own lr·grad independently, so a minibatch applies
    the *sum* of per-pair gradients (not the mean)."""
    return loss_fn(params, centers, contexts, negatives) * centers.shape[0]


@partial(jax.jit, donate_argnums=0)
def train_step_dense(
    params: dict,
    centers: jax.Array,
    contexts: jax.Array,
    negatives: jax.Array,
    lr: jax.Array,
) -> tuple[dict, jax.Array]:
    sum_loss, grads = jax.value_and_grad(sum_loss_fn)(
        params, centers, contexts, negatives)
    params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return params, sum_loss / centers.shape[0]


def sparse_row_grads_per_pair(
    w: jax.Array, c_pos: jax.Array, c_neg: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-pair losses + per-row gradients of the *sum* SGNS loss — the
    shared core of :func:`sparse_row_grads` and the fused Pallas kernels
    (which need the loss un-reduced, one value per pair). Keeping one
    copy of these expressions is what the kernels' bit-equivalence
    contract stands on.

    Returns (loss (B,), dW_rows (B,d), dC_pos_rows (B,d),
    dC_neg_rows (B,K,d)).
    """
    # Products and sums, not einsums: Mosaic has no lowering for these
    # batched contractions, and the elementwise form is exact f32 on
    # every backend (no reduced-precision matmul passes).
    s_pos = jnp.sum(w * c_pos, axis=-1)
    s_neg = jnp.sum(w[:, None, :] * c_neg, axis=-1)
    loss = -jax.nn.log_sigmoid(s_pos) - jnp.sum(
        jax.nn.log_sigmoid(-s_neg), axis=-1)
    g_pos = jax.nn.sigmoid(s_pos) - 1.0                # (B,)
    g_neg = jax.nn.sigmoid(s_neg)                      # (B,K)
    d_w = g_pos[:, None] * c_pos + jnp.sum(g_neg[..., None] * c_neg, axis=1)
    d_cp = g_pos[:, None] * w
    d_cn = g_neg[..., None] * w[:, None, :]
    return loss, d_w, d_cp, d_cn


def sparse_row_grads(
    w: jax.Array, c_pos: jax.Array, c_neg: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-row gradients of the *sum* SGNS loss (word2vec semantics;
    matches autodiff of :func:`sum_loss_fn` exactly).

    Returns (mean_loss, dW_rows (B,d), dC_pos_rows (B,d), dC_neg_rows (B,K,d)).
    This is the function the Pallas kernel implements.
    """
    loss, d_w, d_cp, d_cn = sparse_row_grads_per_pair(w, c_pos, c_neg)
    return jnp.mean(loss), d_w, d_cp, d_cn


def train_step_sparse(
    params: dict,
    centers: jax.Array,
    contexts: jax.Array,
    negatives: jax.Array,
    lr: jax.Array,
    row_grad_fn=sparse_row_grads,
) -> tuple[dict, jax.Array]:
    """Gather → row grads (jnp or Pallas) → scatter-add. Duplicate indices
    accumulate, exactly like the dense-grad scatter that autodiff builds."""
    w = params["W"][centers]
    c_pos = params["C"][contexts]
    c_neg = params["C"][negatives]
    loss, d_w, d_cp, d_cn = row_grad_fn(w, c_pos, c_neg)
    W = params["W"].at[centers].add(-lr * d_w)
    C = params["C"].at[contexts].add(-lr * d_cp)
    C = C.at[negatives.reshape(-1)].add(-lr * d_cn.reshape(-1, d_cn.shape[-1]))
    return {"W": W, "C": C}, loss


def linear_lr(step: jax.Array, total_steps: int, cfg: SGNSConfig) -> jax.Array:
    """word2vec's linearly decaying alpha."""
    frac = jnp.clip(step / max(total_steps, 1), 0.0, 1.0)
    return jnp.maximum(cfg.lr * (1.0 - frac), cfg.lr_min)


def embedding_matrix(params: dict) -> jax.Array:
    """The word representation the paper evaluates (input vectors W)."""
    return params["W"]
