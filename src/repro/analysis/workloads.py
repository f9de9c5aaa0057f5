"""Shared reference workloads for the analysis passes and benchmarks.

``zipf50k`` — the Zipfian paper-shape direct-step workload whose
``<engine>@zipf50k`` rows in ``BENCH_wallclock.json`` carry the
planner-derived HBM row-traffic columns the CI bench gate compares.
Defined ONCE here so ``benchmarks/bench_wallclock.py`` (which measures
it) and ``repro.analysis.contracts`` (which certifies the committed
traffic numbers against the planner) can never drift apart. The id
construction is deterministic and must stay bit-stable: the committed
baseline rows were produced by exactly these seeds.
"""

from __future__ import annotations

import numpy as np

# V=50k×512 at batch 8192: small blocks maximize cross-block hot-row
# recurrence; the large batch amortizes the per-step hot-prefix DMA
# over 64 blocks.
ZIPF50K = {"V": 50_000, "D": 512, "B": 8192, "K": 5, "BLK": 128,
           "HOT": 2048}

# The ``@zipf50k`` rows, by label: the fused engine's ``hot_rows`` for
# each (0 is the pure-DMA case).
ZIPF50K_ROWS = {"pallas_fused_tiered[hot_rows=0]@zipf50k": 0,
                "pallas_fused_tiered@zipf50k": ZIPF50K["HOT"]}


def zipf50k_ids():
    """The workload's deterministic id streams: ``(centers, contexts,
    negatives, noise_table, key)``. Power-law ids over the
    frequency-sorted vocab (``choice`` keeps the mid-frequency strata
    populated, unlike a raw Zipf draw whose mass all lands on a handful
    of head ids); negatives are the replayed counter-PRNG draw of the
    fused kernel's planner."""
    import jax
    import jax.numpy as jnp

    from repro.data.pairs import build_noise_table
    from repro.kernels.alias_draw import _as_seed, fused_negative_ids

    V, B, K = ZIPF50K["V"], ZIPF50K["B"], ZIPF50K["K"]
    rng = np.random.default_rng(11)
    p = 1.0 / np.arange(1, V + 1) ** 1.05
    p /= p.sum()
    c = jnp.asarray(rng.choice(V, size=B, p=p).astype(np.int32))
    x = jnp.asarray(rng.choice(V, size=B, p=p).astype(np.int32))
    table = build_noise_table((p * 1e6).astype(np.float32), kind="alias")
    key = jax.random.PRNGKey(3)
    neg = fused_negative_ids(_as_seed(key), table["prob"], table["alias"],
                             (B, K))
    return c, x, neg, table, key


def zipf50k_row_traffic(hot_rows: int) -> int:
    """Planner-predicted HBM rows DMA'd per step at this hot-tier
    setting — the ``hbm_rows_per_step`` column of the ``@zipf50k``
    bench rows."""
    from repro.kernels.sgns_fused_pipe import plan_blocks, plan_row_traffic

    c, x, neg, _, _ = zipf50k_ids()
    plan = plan_blocks(c, x, neg, ZIPF50K["V"], ZIPF50K["BLK"],
                       hot_rows=hot_rows)
    return plan_row_traffic(plan, hot_rows=hot_rows)
