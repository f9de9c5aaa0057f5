"""Plain reference of SGNS training through the first chunks of a run.

What the program computes, for each worker, written out in jnp:

* init: the worker's key is ``split(key, workers)[w]``;
  ``W ~ U(-0.5/d, 0.5/d)`` from the first half of ``split(that key)``,
  ``C = 0``;
* chunk ``k`` uses ``split(chunk_key(k), workers)[w]`` and, for step
  ``i``, the second half of its ``i``-th ``split`` (the scan's carry);
* negatives: ``K`` per pair from the worker's Vose alias table over its
  noise distribution, drawn by a counter hash (two rounds of the
  lowbias32 mix) keyed by that step key, two counters per draw over the
  ``(B, K)`` positions in row-major order;
* learning rate ``max(lr · (1 - step/total), lr_min)``;
* update: the batch in blocks of ``block`` pairs; each block's
  gradients of the summed loss ``-log σ(w·c) - Σ log σ(-w·n)`` are
  taken at the tables as the previous block left them and added with
  ``-lr`` (duplicate rows add up);
* a step's loss is the mean over its pairs.

``dtype`` stores the tables in another type (the control: bfloat16);
the arithmetic stays float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def alias_table(q: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vose's alias table of ``q`` (``prob`` float32, ``alias`` int32),
    pairing the last under-full bucket with the last over-full one."""
    p = np.asarray(q, dtype=np.float64)
    V = len(p)
    scaled = p * (V / p.sum())
    prob = np.ones(V, dtype=np.float64)
    alias = np.arange(V, dtype=np.int32)
    small = [i for i in range(V) if scaled[i] < 1.0]
    large = [i for i in range(V) if scaled[i] >= 1.0]
    while small and large:
        lo, hi = small.pop(), large.pop()
        prob[lo] = scaled[lo]
        alias[lo] = hi
        scaled[hi] = (scaled[hi] + scaled[lo]) - 1.0
        (small if scaled[hi] < 1.0 else large).append(hi)
    for i in small + large:
        prob[i] = 1.0
        alias[i] = i
    return prob.astype(np.float32), alias


def _mix(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def _uniform(seed, counters):
    bits = _mix(_mix(counters ^ seed[0]) + seed[1])
    return (bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * jnp.float32(1 / (1 << 24))


def negatives(seed, prob, alias, batch: int, k: int):
    """``(batch, k)`` noise ids for one step."""
    base = jnp.arange(batch * k, dtype=jnp.uint32).reshape(batch, k)
    u_idx = _uniform(seed, base * jnp.uint32(2))
    u_acc = _uniform(seed, base * jnp.uint32(2) + jnp.uint32(1))
    V = prob.shape[0]
    idx = jnp.minimum((u_idx * V).astype(jnp.int32), V - 1)
    return jnp.where(u_acc < prob[idx], idx, alias[idx])


def init_table(key, workers: int, w: int, V: int, d: int,
               dtype=jnp.float32):
    """Worker ``w``'s initial ``(W, C)``."""
    kw, _ = jax.random.split(jax.random.split(key, workers)[w])
    W = jax.random.uniform(kw, (V, d), jnp.float32, -0.5 / d, 0.5 / d)
    return W.astype(dtype), jnp.zeros((V, d), dtype)


def step_seeds(chunk_key, workers: int, w: int, steps: int):
    """``(steps, 2)`` uint32 keys of worker ``w``'s steps in one chunk."""
    def body(key, _):
        key, sub = jax.random.split(key)
        return key, sub
    k = jax.random.split(chunk_key, workers)[w]
    return jax.lax.scan(body, k, None, length=steps)[1].astype(jnp.uint32)


def _block(W, C, cen, ctx, neg, lr):
    w, cp, cn = (W[cen].astype(jnp.float32), C[ctx].astype(jnp.float32),
                 C[neg].astype(jnp.float32))
    s_pos = jnp.sum(w * cp, axis=-1)
    s_neg = jnp.sum(w[:, None, :] * cn, axis=-1)
    loss = -jax.nn.log_sigmoid(s_pos) - jnp.sum(jax.nn.log_sigmoid(-s_neg), -1)
    g_pos = jax.nn.sigmoid(s_pos) - 1.0
    g_neg = jax.nn.sigmoid(s_neg)
    d_w = g_pos[:, None] * cp + jnp.sum(g_neg[..., None] * cn, axis=1)
    d_cp = g_pos[:, None] * w
    d_cn = g_neg[..., None] * w[:, None, :]
    dt = W.dtype
    W = W.at[cen].add((-lr * d_w).astype(dt))
    C = C.at[ctx].add((-lr * d_cp).astype(dt))
    C = C.at[neg.reshape(-1)].add((-lr * d_cn).reshape(-1, W.shape[-1])
                                  .astype(dt))
    return W, C, jnp.sum(loss)


@partial(jax.jit, static_argnames=("k", "block", "total_steps", "lr0",
                                   "lr_min", "keep"),
         donate_argnums=(0, 1))
def train_chunk(W, C, centers, contexts, prob, alias, seeds, step0, *,
                k: int, block: int, total_steps: int, lr0: float,
                lr_min: float, keep: float = 1.0):
    """One chunk of one worker: tables ``(V, d)``, ids ``(S, B)``, its
    alias table ``(V,)``, ``seeds (S, 2)``. Returns the tables and the
    ``(S,)`` step losses. ``keep < 1`` trains on that leading share of
    each batch only (a fault the checks must catch)."""
    B = centers.shape[-1]
    used = int(B * keep)

    def step(carry, xs):
        W, C, i = carry
        c, x, seed = xs
        neg = negatives(seed, prob, alias, B, k)
        frac = jnp.clip((step0 + i) / max(total_steps, 1), 0.0, 1.0)
        lr = jnp.maximum(lr0 * (1.0 - frac), lr_min)
        total = jnp.float32(0)
        for b0 in range(0, used, block):
            b1 = min(b0 + block, used)
            W, C, s = _block(W, C, c[b0:b1], x[b0:b1], neg[b0:b1], lr)
            total = total + s
        return (W, C, i + 1), total / used

    (W, C, _), losses = jax.lax.scan(step, (W, C, jnp.int32(0)),
                                     (centers, contexts, seeds))
    return W, C, losses


@jax.jit
def change_norms(W1, C1, W0, C0):
    """Norms of the W and C change, in float32 (for stacked tables, one
    pair per leading index)."""
    def norm(a, b):
        diff = a.astype(jnp.float32) - b.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(diff * diff, axis=(-2, -1)))
    return jnp.stack([norm(W1, W0), norm(C1, C0)], axis=-1)
