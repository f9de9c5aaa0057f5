"""Fully-fused Pallas SGNS step: in-kernel alias negative sampling +
forward + row grads + parameter apply, one VMEM pass.

The partially-fused path (``sgns_update.py``) still leaves two HBM seams
around the kernel: the negative-id draw (an XLA op between the sampler
tables and the gather) and the gather→grad→scatter round-trips for the
``(B, K, d)`` negative rows. This kernel closes both: the alias
``prob``/``alias`` tables are kernel operands, the K negatives per pair
are drawn *inside* the kernel from a counter-based PRNG, and the step's
scatter-add apply happens on the VMEM-resident tables — negative ids and
the ``(B, K)`` logit/grad intermediates never exist as HBM arrays. Both
parameter tables are input/output-aliased, so the step is in-place at
the XLA level too.

PRNG: a stateless counter hash (two rounds of the lowbias32 avalanche
mix) keyed by the step's ``(2,)`` uint32 PRNG key. It is plain uint32
arithmetic (the uniform is cast through int32, which Mosaic lowers), and
:func:`fused_negative_ids` reproduces it outside the kernel —
that is what lets the equivalence tests feed identical negatives to the
``sparse`` reference. (``pltpu.prng_random_bits`` would be faster on TPU
but is neither available in interpret mode nor replayable off-device.)

Semantics match :func:`repro.core.sgns.train_step_sparse` exactly: all
row gradients are computed from the pre-step tables, then applied with
accumulating scatter-adds (duplicate ids add up).

Capacity: both ``(V, d)`` tables ride through the kernel whole, so this
variant targets per-worker sub-model tables that fit VMEM-adjacent
memory (the paper's 300k×500 tables need the blocked HBM-streaming
variant). Interpret mode has no such limit, and it is the only mode:
the row gathers and scatter-adds by vectors of ids have no Mosaic
lowering (``NO_MOSAIC``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import require_interpret

# Why the VMEM-resident kernel and its sampler have no Mosaic lowering.
NO_MOSAIC = ("it gathers and scatter-adds table rows, and draws alias-table "
              "entries, by vectors of ids, and Mosaic has no vector gather "
              "or scatter on VMEM values")


# ---------------------------------------------------------------------------
# Counter-based PRNG (stateless, replayable, uint32-only)
# ---------------------------------------------------------------------------
def _mix32(x: jax.Array) -> jax.Array:
    """lowbias32 avalanche hash round (uint32 → uint32, bijective)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def counter_uniforms(seed: jax.Array, counters: jax.Array) -> jax.Array:
    """U[0,1) float32 per counter, keyed by a ``(2,)`` uint32 seed.

    Distinct counters give independent-looking streams (each draw is a
    double avalanche hash of its own counter); distinct seeds give
    disjoint streams for the same counters.
    """
    seed = seed.astype(jnp.uint32)
    bits = _mix32(_mix32(counters.astype(jnp.uint32) ^ seed[0]) + seed[1])
    # top 24 bits → exactly representable uniforms in [0, 1); through
    # int32 (exact below 2**24), since Mosaic has no uint32 → f32 cast
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1 / (1 << 24))


def alias_draw_from_counters(
    seed: jax.Array, prob: jax.Array, alias: jax.Array, base: jax.Array
) -> jax.Array:
    """One alias-table draw per counter in ``base`` (draws' global
    row-major positions): two sub-counters per draw (index pick +
    alias-acceptance), top-24-bit uniforms, min-clamped index. The ONE
    copy of the draw expressions — both the VMEM-resident and the
    HBM-blocked kernels, and the off-kernel replay, call this, which is
    what keeps their draws bit-identical by construction."""
    u_idx = counter_uniforms(seed, base * jnp.uint32(2))
    u_acc = counter_uniforms(seed, base * jnp.uint32(2) + jnp.uint32(1))
    V = prob.shape[0]
    idx = jnp.minimum((u_idx * V).astype(jnp.int32), V - 1)
    return jnp.where(u_acc < prob[idx], idx, alias[idx]).astype(jnp.int32)


def fused_negative_ids(
    seed: jax.Array, prob: jax.Array, alias: jax.Array, shape: tuple[int, ...]
) -> jax.Array:
    """The in-kernel negative draw, as a pure-jnp function of values.

    The kernel body calls this on its VMEM-resident table values; tests
    call it on the same ``(prob, alias)`` arrays to replay the exact ids
    a fused step drew (same ``seed`` ⇒ same negatives). Counters are
    assigned row-major over ``shape``, two per draw (index pick +
    alias-acceptance).
    """
    n = 1
    for s in shape:
        n *= s
    base = jnp.arange(n, dtype=jnp.uint32).reshape(shape)
    return alias_draw_from_counters(seed, prob, alias, base)


# ---------------------------------------------------------------------------
# The fused kernel
# ---------------------------------------------------------------------------
def _sgns_fused_kernel(K, seed_ref, lr_ref, w_ref, c_ref, cen_ref, ctx_ref,
                       prob_ref, alias_ref, w_out_ref, c_out_ref, loss_ref):
    W = w_ref[...].astype(jnp.float32)            # (V, d)
    C = c_ref[...].astype(jnp.float32)            # (V, d)
    cen = cen_ref[...]                            # (B,)
    ctx = ctx_ref[...]                            # (B,)
    lr = lr_ref[0]

    # 1. draw the K negatives per pair — ids live only in VMEM/registers
    ids = fused_negative_ids(seed_ref[...], prob_ref[...], alias_ref[...],
                             (cen.shape[0], K))

    # 2. gather all rows from the resident tables
    w = W[cen]                                    # (B, d)
    cp = C[ctx]                                   # (B, d)
    cn = C[ids]                                   # (B, K, d)

    # 3. stable log σ forward + all three row grads, one pass
    s_pos = jnp.sum(w * cp, axis=-1)              # (B,)
    s_neg = jnp.sum(w[:, None, :] * cn, axis=-1)  # (B, K)

    def softplus(x):
        return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))

    loss = softplus(-s_pos) + jnp.sum(softplus(s_neg), axis=-1)
    g_pos = jax.nn.sigmoid(s_pos) - 1.0           # (B,)
    g_neg = jax.nn.sigmoid(s_neg)                 # (B, K)

    dw = g_pos[:, None] * cp + jnp.sum(g_neg[:, :, None] * cn, axis=1)
    dcp = g_pos[:, None] * w
    dcn = g_neg[:, :, None] * w[:, None, :]

    # 4. apply — accumulating scatter-adds on the resident tables
    #    (word2vec sum-loss semantics: grads from pre-step params)
    W = W.at[cen].add(-lr * dw)
    C = C.at[ctx].add(-lr * dcp)
    C = C.at[ids.reshape(-1)].add(-lr * dcn.reshape(-1, dcn.shape[-1]))

    w_out_ref[...] = W.astype(w_out_ref.dtype)
    c_out_ref[...] = C.astype(c_out_ref.dtype)
    loss_ref[...] = loss[:, None]                 # per-pair loss, (B, 1)


def _as_seed(key: jax.Array) -> jax.Array:
    """(2,) uint32 seed from a raw or typed JAX PRNG key."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("negatives", "interpret"))
def sgns_fused_step(
    params: dict,
    centers: jax.Array,
    contexts: jax.Array,
    table: dict,
    key: jax.Array,
    lr: jax.Array,
    *,
    negatives: int = 5,
    interpret: bool | None = None,
) -> tuple[dict, jax.Array]:
    """One whole SGNS step in a single ``pallas_call`` (interpret mode
    only: see ``NO_MOSAIC``).

    params: ``{"W": (V, d), "C": (V, d)}``; centers/contexts ``(B,)``
    int32; table: ``{"prob": (V,), "alias": (V,)}`` Vose alias table of
    the worker's unigram^0.75 noise distribution; key: ``(2,)`` uint32.
    Returns ``(params', mean_loss)`` — bit-identical to
    ``train_step_sparse`` fed the ids :func:`fused_negative_ids` yields
    for the same key.
    """
    interpret = require_interpret(interpret, "sgns_fused_step", NO_MOSAIC)
    V, d = params["W"].shape
    B = centers.shape[0]
    out = pl.pallas_call(
        functools.partial(_sgns_fused_kernel, negatives),
        out_shape=[
            jax.ShapeDtypeStruct((V, d), params["W"].dtype),
            jax.ShapeDtypeStruct((V, d), params["C"].dtype),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        # W/C are updated in place: operands 2, 3 alias outputs 0, 1.
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
    )(_as_seed(key), jnp.reshape(lr, (1,)).astype(jnp.float32),
      params["W"], params["C"], centers, contexts,
      table["prob"], table["alias"])
    return {"W": out[0], "C": out[1]}, jnp.mean(out[2][:, 0])


# ---------------------------------------------------------------------------
# Standalone in-kernel sampler (test/benchmark surface for the draw path)
# ---------------------------------------------------------------------------
def _sampler_kernel(seed_ref, prob_ref, alias_ref, out_ref):
    out_ref[...] = fused_negative_ids(
        seed_ref[...], prob_ref[...], alias_ref[...], out_ref.shape)


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def sample_negatives_fused(
    table: dict, key: jax.Array, shape: tuple[int, ...],
    *, interpret: bool | None = None,
) -> jax.Array:
    """Draw negative ids with the *kernel's* sampler, via pallas_call.

    Same ``fn(table, key, shape)`` contract as the samplers in
    ``repro.data.pairs`` — used by the chi-square goodness-of-fit tests
    to validate the in-kernel draw path itself, and as the fused
    engine's reference draw outside the kernel.
    """
    interpret = require_interpret(interpret, "sample_negatives_fused",
                                  NO_MOSAIC)
    return pl.pallas_call(
        _sampler_kernel,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
        interpret=interpret,
    )(_as_seed(key), table["prob"], table["alias"])
