"""SGNS step micro-benchmark: jnp reference path throughput (CPU-real),
Pallas-kernel equivalence check (interpret mode; Mosaic on TPU), and an
update-engine smoke sweep — one timed step per registered engine, so the
benchmark artifact shows every step path (dense / sparse / pallas /
pallas_fused_tiered) side by side, including the fused engine's
bit-equivalence against the per-block sparse reference, with and
without its hot tier.

A **hot-fraction sweep** times ``pallas_fused_tiered`` over a ladder of
``hot_rows`` settings on a Zipfian pair stream — the VMEM-budget vs
DMA-traffic trade-off curve, landed in the CI bench artifact (a compact
ladder rides in every ``run()``; ``--hot-sweep`` prints a finer
standalone one)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timer
from repro.core import sgns
from repro.core.engine import ENGINE_NAMES, get_engine
from repro.data.pairs import build_noise_table
from repro.kernels import ops, ref


def _bench(fn, args, iters=20):
    jax.block_until_ready(fn(*args))  # warmup/compile
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # µs


def engine_sweep(cfg, params, c, x, counts, iters=10, specs=ENGINE_NAMES):
    """Time one engine step per spec (same data, own table layout).
    Returns {engine_spec: µs_per_step} — specs may carry a sampler
    suffix ("sparse:alias"), which is honored, not stripped."""
    out = {}
    for name in specs:
        eng = get_engine(name)
        table = build_noise_table(counts, kind=eng.table_kind)
        step = jax.jit(eng.make_step(cfg, total_steps=1000))
        key = jax.random.PRNGKey(3)
        p0 = jax.tree.map(jnp.copy, params)
        us = _bench(lambda: step(p0, c, x, table, key, jnp.int32(1)), (),
                    iters=iters)
        out[str(name)] = us
    return out


def zipf_ids(rng, V, shape, a=1.2):
    """Zipfian ids clipped to the vocab — the skewed stream the hot
    tier is built for (ids are frequency-ranked, so low id = hot)."""
    return jnp.asarray(np.minimum(rng.zipf(a, shape) - 1, V - 1)
                       .astype(np.int32))


def hot_sweep(cfg, params, counts, hots, B=1024, iters=3, seed=7):
    """Time ``pallas_fused_tiered`` at a ladder of ``hot_rows`` settings
    on a Zipfian pair stream (uniform ids would starve the hot tier).
    Returns ``[{"hot_rows": k, "us": µs_per_step}, ...]`` — the
    VMEM-budget/speed trade-off curve; ``hot_rows=0`` is the kernel's
    pure-DMA baseline."""
    rng = np.random.default_rng(seed)
    V = cfg.vocab_size
    c = zipf_ids(rng, V, B)
    x = zipf_ids(rng, V, B)
    table = build_noise_table(counts, kind="alias")
    rows = []
    for k in hots:
        eng = get_engine("pallas_fused_tiered", hot_rows=int(k))
        step = jax.jit(eng.make_step(cfg, total_steps=1000))
        key = jax.random.PRNGKey(3)
        p0 = jax.tree.map(jnp.copy, params)
        us = _bench(lambda: step(p0, c, x, table, key, jnp.int32(1)), (),
                    iters=iters)
        rows.append({"hot_rows": int(k), "us": us})
    return rows


def run(B=1024, K=5, D=512, V=50_000, quick=False, engines=ENGINE_NAMES):
    cfg = sgns.SGNSConfig(vocab_size=V, dim=D, negatives=K)
    params = sgns.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.integers(0, V, B, dtype=np.int32))
    x = jnp.asarray(rng.integers(0, V, B, dtype=np.int32))
    n = jnp.asarray(rng.integers(0, V, (B, K), dtype=np.int32))
    lr = jnp.float32(0.025)

    sparse = jax.jit(sgns.train_step_sparse)
    dense = jax.jit(sgns.train_step_dense.__wrapped__)  # no buffer donation
    us_sparse = _bench(lambda: sparse(params, c, x, n, lr), ())
    us_dense = _bench(lambda: dense(params, c, x, n, lr), ())

    # kernel equivalence (interpret): correctness, not speed, on CPU
    w = params["W"][c]
    cp = params["C"][x]
    cn = params["C"][n]
    lk, dwk, _, _ = ops.sgns_row_grads(w, cp, cn, interpret=True)
    lr_, dwr, _, _ = ref.sgns_row_grads_ref(w, cp, cn)
    err = float(jnp.max(jnp.abs(dwk - dwr)))

    # the fused engine vs the per-block sparse reference on the same
    # replayed negatives, at hot_rows 0 (pure DMA) and its default: the
    # step must be *bit-identical* (the hot prefix is genuinely touched:
    # the noise draw is Zipfian over frequency-ranked ids)
    counts = rng.zipf(1.3, V).astype(np.float64)
    eng_t = get_engine("pallas_fused_tiered")
    table = build_noise_table(counts, kind="alias")
    key = jax.random.PRNGKey(9)
    ids = eng_t.sample(table, key, (B, K))
    blk = eng_t.block_pairs
    sparse_jit = jax.jit(sgns.train_step_sparse)
    pr = jax.tree.map(jnp.copy, params)
    lr0 = sgns.linear_lr(jnp.int32(0), 1000, cfg)
    for b0 in range(0, B, blk):
        pr, _ = sparse_jit(pr, c[b0:b0 + blk], x[b0:b0 + blk],
                           ids[b0:b0 + blk], lr0)
    tiered_err = 0.0
    for hot in (0, eng_t.hot_rows):
        pt, _ = get_engine(eng_t, hot_rows=hot).make_step(cfg, 1000)(
            jax.tree.map(jnp.copy, params), c, x, table, key, jnp.int32(0))
        tiered_err = max(tiered_err, float(max(
            jnp.max(jnp.abs(pt["W"] - pr["W"])),
            jnp.max(jnp.abs(pt["C"] - pr["C"])))))

    engine_us = engine_sweep(cfg, params, c, x, counts,
                             iters=3 if quick else 10, specs=engines)
    sweep = hot_sweep(cfg, params, counts,
                      hots=(0, 256, 4096) if quick else (0, 64, 256, 1024,
                                                         4096, V),
                      B=B, iters=2 if quick else 5)
    return {
        "us_sparse_step": us_sparse,
        "us_dense_step": us_dense,
        "pairs_per_s_sparse": B / (us_sparse / 1e6),
        "kernel_max_err": err,
        "fused_tiered_vs_sparse_err": tiered_err,
        "engine_us": engine_us,
        "tiered_hot_sweep": sweep,
        "B": B,
    }


def main(quick=False, engine=None):
    """``engine`` (name or spec string) restricts the sweep to one
    engine — ``python -m benchmarks.bench_kernel --engine sparse``."""
    if engine is not None:
        get_engine(engine)                  # validate the spec up front
    specs = ENGINE_NAMES if engine is None else (engine,)
    with timer() as t:
        r = run(quick=quick, engines=specs)
    print(f"\n[kernel] SGNS step micro-bench ({t.s:.1f}s)")
    print(f"sparse step: {r['us_sparse_step']:9.1f} µs/call "
          f"({r['pairs_per_s_sparse']:.2e} pairs/s on 1 CPU)")
    print(f"dense  step: {r['us_dense_step']:9.1f} µs/call "
          f"(materializes (V,d) grad — the path the sparse step replaces)")
    print(f"pallas kernel vs oracle max|Δ| = {r['kernel_max_err']:.2e} "
          f"(interpret mode)")
    print(f"pallas_fused_tiered step vs per-block sparse ref max|Δ| = "
          f"{r['fused_tiered_vs_sparse_err']:.2e} (hot_rows 0 and default: "
          f"VMEM hot prefix + cold DMA ring; bit-identical by contract)")
    for name, us in r["engine_us"].items():
        print(f"engine {name:12s}: {us:9.1f} µs/step "
              f"({r['B'] / (us / 1e6):.2e} pairs/s)")
    print("tiered hot-fraction sweep (Zipfian stream; hot_rows → µs/step):")
    for row in r["tiered_hot_sweep"]:
        print(f"  hot_rows {row['hot_rows']:6d}: {row['us']:9.1f} µs/step "
              f"({r['B'] / (row['us'] / 1e6):.2e} pairs/s)")
    return r


def main_hot_sweep(quick=False, B=1024, K=5, D=512, V=50_000):
    """Standalone fine-grained hot-fraction ladder — the VMEM-budget vs
    speed trade-off of ``pallas_fused_tiered`` on a Zipfian stream."""
    cfg = sgns.SGNSConfig(vocab_size=V, dim=D, negatives=K)
    params = sgns.init_params(jax.random.PRNGKey(0), cfg)
    counts = np.random.default_rng(0).zipf(1.3, V).astype(np.float64)
    hots = (0, 256, 4096) if quick else (0, 16, 64, 256, 1024, 4096,
                                         16_384, V)
    with timer() as t:
        rows = hot_sweep(cfg, params, counts, hots, B=B,
                         iters=2 if quick else 5)
    print(f"\n[kernel] pallas_fused_tiered hot-fraction sweep "
          f"(V={V}, d={D}, B={B}, Zipfian ids; {t.s:.1f}s)")
    for row in rows:
        vmem_mb = 2 * row["hot_rows"] * D * 4 / 1e6
        print(f"  hot_rows {row['hot_rows']:6d} "
              f"({vmem_mb:7.2f} MB VMEM): {row['us']:9.1f} µs/step "
              f"({B / (row['us'] / 1e6):.2e} pairs/s)")
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default=None,
                    help="time only this engine's step (dense | sparse | "
                         "pallas | pallas_fused_tiered)")
    ap.add_argument("--hot-sweep", action="store_true",
                    help="run only the fine-grained pallas_fused_tiered "
                         "hot-fraction ladder (VMEM budget vs µs/step)")
    ap.add_argument("--quick", action="store_true")
    a = ap.parse_args()
    if a.hot_sweep:
        main_hot_sweep(quick=a.quick)
    else:
        main(quick=a.quick, engine=a.engine)
