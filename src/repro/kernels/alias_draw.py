"""The counter-PRNG alias draw of the fused kernel's negatives.

A stateless counter hash (two rounds of the lowbias32 avalanche mix)
keyed by the step's ``(2,)`` uint32 PRNG key picks each negative from
the worker's Vose alias table. It is plain uint32 arithmetic (the
uniform is cast through int32, which Mosaic lowers), so the same
expressions serve the kernel's block planner
(:func:`repro.kernels.sgns_fused_pipe.fused_pipe_rows_step`), which
replays the draw because its row dedup needs the ids, and the tests,
which feed the replayed ids to the ``sparse`` reference.
(``pltpu.prng_random_bits`` would be faster on TPU but is neither
available in interpret mode nor replayable off-device.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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
    alias-acceptance), top-24-bit uniforms, min-clamped index. The one
    copy of the draw expressions."""
    u_idx = counter_uniforms(seed, base * jnp.uint32(2))
    u_acc = counter_uniforms(seed, base * jnp.uint32(2) + jnp.uint32(1))
    V = prob.shape[0]
    idx = jnp.minimum((u_idx * V).astype(jnp.int32), V - 1)
    return jnp.where(u_acc < prob[idx], idx, alias[idx]).astype(jnp.int32)


def fused_negative_ids(
    seed: jax.Array, prob: jax.Array, alias: jax.Array, shape: tuple[int, ...]
) -> jax.Array:
    """The fused step's negative draw, as a pure-jnp function of values.

    The kernel's planner calls this on the step's ``(prob, alias)``
    tables; tests call it on the same arrays to replay the exact ids a
    fused step drew (same ``seed`` ⇒ same negatives). Counters are
    assigned row-major over ``shape``, two per draw (index pick +
    alias-acceptance).
    """
    n = 1
    for s in shape:
        n *= s
    base = jnp.arange(n, dtype=jnp.uint32).reshape(shape)
    return alias_draw_from_counters(seed, prob, alias, base)


def _as_seed(key: jax.Array) -> jax.Array:
    """(2,) uint32 seed from a raw or typed JAX PRNG key."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key.astype(jnp.uint32)
