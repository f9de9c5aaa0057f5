"""Worker-mesh execution of the merge phase's sharded Gram reduction.

This is the **one intentional collective in the system**. Training is
zero-collective by design (the paper's headline property, certified by
``repro.analysis.contracts``); the merge phase is the single
synchronization point, and when its Gram accumulations
(:func:`repro.core.merge.sharded_gram`) run distributed over the
``worker`` mesh, the partial row-block Grams must be gathered before the
fixed-order reduction. That gather — one ``all_gather`` of ``(S, d, d)``
partials, tiny next to the ``(V, d)`` tables — is the only collective
the merge emits, and it is deliberately **outside** the RL004
zero-collective lint scope (see :mod:`repro.analysis.lint_rules`, which
covers the train path: ``kernels/``, ``data/``, ``core/engine.py``,
``core/sgns.py``).

Bit-identity contract (tested in ``tests/test_merge.py``): the mesh path
computes exactly the same per-block partials as the local path
(placement never changes a block's bits) and reduces them in the same
ascending block order (a sequential scan over the gathered stack — not a
``psum``, whose reduction order is implementation-defined), so
``mesh_sharded_gram(A, B, mesh, num_shards=S)`` equals
``sharded_gram(A, B, S)`` bit-for-bit on any device count dividing S.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.merge import gram_block_partials, reduce_gram_partials


def mesh_sharded_gram(A: jax.Array, B: jax.Array, mesh, *,
                      num_shards: int | None = None,
                      axis: str = "worker") -> jax.Array:
    """``AᵀB`` computed distributed over ``mesh``'s ``axis``: each
    device owns a contiguous row slice of ``A``/``B``, computes its
    ``num_shards / n_devices`` block partials locally, all-gathers the
    ``(num_shards, d, e)`` partial stack, and reduces it in ascending
    block order — bit-identical to the single-host
    :func:`~repro.core.merge.sharded_gram` at the same ``num_shards``.

    ``num_shards`` defaults to the mesh axis size and must be a
    multiple of it; row counts must divide evenly (the ALiR caller works
    on fixed ``(V, d)`` tables — pad upstream if V is ragged).
    """
    n_dev = mesh.shape[axis]
    S = int(num_shards) if num_shards is not None else n_dev
    if S % n_dev:
        raise ValueError(f"num_shards {S} must be a multiple of the mesh "
                         f"axis size {n_dev}")
    V = A.shape[0]
    if V % S:
        raise ValueError(f"rows {V} must divide evenly into {S} shards "
                         f"(pad upstream)")
    per_dev = S // n_dev

    def local(a, b):
        parts = gram_block_partials(a, b, per_dev)
        # The merge phase's one intentional collective: gather every
        # device's block partials so each replica can run the same
        # canonical fixed-order reduction.
        # repro-lint: ignore[RL004]
        allp = jax.lax.all_gather(parts, axis, tiled=True)
        return reduce_gram_partials(allp)

    f = jax.shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                      out_specs=P(), check_vma=False)
    return f(jnp.asarray(A), jnp.asarray(B))


def lower_mesh_gram(V: int, d: int, mesh, *,
                    num_shards: int | None = None, axis: str = "worker"):
    """Lowered (StableHLO) mesh Gram for the analysis layer: the
    certifier counts exactly one ``all_gather`` here — the allow-listed
    merge collective — while the train path stays zero-collective."""
    spec = jax.ShapeDtypeStruct((V, d), jnp.float32)
    fn = jax.jit(lambda A, B: mesh_sharded_gram(
        A, B, mesh, num_shards=num_shards, axis=axis))
    return fn.lower(spec, spec)
