#!/usr/bin/env python3
"""Smoke run of the training path on a TPU, at the paper's width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip: the HBM kernel of ``pallas_fused_tiered``, at ``hot_rows`` 0
(pure DMA) and 256, takes one step at 300k×500 and is checked
against the per-block ``train_step_sparse`` reference fed the replayed
negatives; then ``run_pipeline`` trains 4 workers at the shape of
``configs/sgns_wiki.py`` (d = 500, window 10, K = 5) over a generated
corpus whose union vocabulary has at least 250,000 words, once with
``sparse:alias`` and once with the compiled ``pallas_fused_tiered``
kernel, and ALiR-merges the kernel run's sub-models.

``--four-chips`` runs only the sharded path and what it is compared
with: 8 workers, 2 per chip, under ``shard_map`` over a worker mesh of
the four chips, against the same 8 workers on one chip under ``vmap``,
on the same seeds.

Every phase checks its result and raises on a failure, so the script
exits non-zero without printing the last line. It exits non-zero before
running anything when JAX finds no TPU, or when the repository's
``src/`` is not beside it. The last line of standard output is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Reference check: one step at the paper's table shape.
CHECK_VOCAB, CHECK_BATCH, CHECK_BLOCK = 300_000, 512, 256
RTOL, ATOL = 1e-5, 1e-7
# Training: 1M generated sentences over a 300k raw vocabulary, sampled
# at rate 1/4 per worker with min_count 1 (base_min_count / workers),
# give a 256,218-word union vocabulary.
RAW_VOCAB, SENTENCES, BASE_MIN_COUNT = 300_000, 1_000_000, 4
MIN_UNION_VOCAB = 250_000
WORKERS, BATCH, STEPS, STEPS_PER_CHUNK = 4, 512, 256, 128
# Four chips: 8 workers over a vocabulary that one chip also holds.
FOUR_RAW_VOCAB, FOUR_SENTENCES, FOUR_WORKERS, FOUR_STEPS = (
    150_000, 300_000, 8, 64)


def fail(msg: str) -> None:
    print(f"FAILED: {msg}", file=sys.stderr)
    raise SystemExit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def device_facts(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def sgns_config():
    from repro.configs.sgns_wiki import CONFIG
    from repro.core.sgns import SGNSConfig

    return SGNSConfig(vocab_size=0, dim=CONFIG.dim, window=CONFIG.window,
                      negatives=CONFIG.negatives, lr=CONFIG.lr)


# ---------------------------------------------------------------------------
def check_kernels_against_reference(V: int, d: int, B: int, blk: int,
                                    K: int, hot_rows: int = 256) -> None:
    """One step of the HBM kernel, without and with its hot tier,
    against the per-block sparse reference on the replayed counter-PRNG
    negatives."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sgns import train_step_sparse
    from repro.data.pairs import build_noise_table
    from repro.kernels.alias_draw import _as_seed, fused_negative_ids
    from repro.kernels.sgns_fused_pipe import fused_pipe_step

    rng = np.random.default_rng(0)
    zipf = 1.0 / np.arange(1, V + 1) ** 1.05
    zipf /= zipf.sum()
    kw, kc = jax.random.split(jax.random.PRNGKey(0))
    # trained-scale tables (word2vec's init range), C non-zero so every
    # term of the gradients is exercised
    params = {k: jax.random.uniform(key, (V, d), jnp.float32,
                                    -0.5 / d, 0.5 / d)
              for k, key in (("W", kw), ("C", kc))}
    c = jnp.asarray(rng.choice(V, size=B, p=zipf).astype(np.int32))
    x = jnp.asarray(rng.choice(V, size=B, p=zipf).astype(np.int32))
    table = build_noise_table((zipf * 1e6).astype(np.float32), kind="alias")
    key = jax.random.PRNGKey(3)
    lr = jnp.float32(0.025)

    ids = fused_negative_ids(_as_seed(key), table["prob"], table["alias"],
                             (B, K))
    step = jax.jit(train_step_sparse)
    ref = params
    for b0 in range(0, B, blk):
        ref, _ = step(ref, c[b0:b0 + blk], x[b0:b0 + blk],
                      ids[b0:b0 + blk], lr)
    ref = jax.tree.map(np.asarray, ref)

    for hot in (0, hot_rows):
        name = f"pallas_fused_tiered hot_rows={hot}"
        t0 = time.perf_counter()
        out, loss = fused_pipe_step(
            params, c, x, table, key, lr, negatives=K, block_pairs=blk,
            ring_depth=2, hot_rows=hot)
        out = jax.tree.map(np.asarray, out)
        secs = time.perf_counter() - t0
        for t in ("W", "C"):
            diff = float(np.max(np.abs(out[t] - ref[t])))
            changed = int(np.count_nonzero(out[t] != np.asarray(params[t])))
            print(f"reference check {name} {t}: max |kernel - reference| = "
                  f"{diff!r} over {V}x{d} (rows changed by the step: "
                  f"{changed // d}), first call incl. compile {secs:.1f}s")
            check(np.allclose(out[t], ref[t], rtol=RTOL, atol=ATOL),
                  f"{name} {t} disagrees with the sparse reference "
                  f"(max abs diff {diff})")
        check(math.isfinite(float(loss)), f"{name} loss is not finite")


def train_one_chip(corpus, cfg) -> None:
    """run_pipeline with both engines; ALiR merge of the kernel run."""
    import jax
    import numpy as np

    from repro.core.driver import run_pipeline
    from repro.core.engine import get_engine
    from repro.kernels.mode import resolve_interpret

    for spec in ("sparse:alias", "pallas_fused_tiered"):
        engine = get_engine(spec)
        kernel = spec.startswith("pallas")
        interpret = resolve_interpret(engine.interpret) if kernel else None
        check(interpret is not True, f"{spec} would run in interpret mode")
        merges = ("alir_pca",) if kernel else ()
        res = run_pipeline(
            corpus, RAW_VOCAB, strategy="random", num_workers=WORKERS,
            cfg=cfg, merge_methods=merges, epochs=1, batch_size=BATCH,
            max_steps_per_epoch=STEPS, steps_per_chunk=STEPS_PER_CHUNK,
            base_min_count=BASE_MIN_COUNT, engine=engine, seed=0)
        V = res.union_vocab.size
        n, V_tab, d = res.stacked.models.shape
        print(f"train {engine.describe()} interpret={interpret}: "
              f"union V={V} d={d} workers={n} "
              f"steps={res.timings['steps_per_epoch']} "
              f"losses={res.losses!r} train_s={res.timings['train_s']:.2f} "
              f"(incl. compile) peak_bytes={peak_bytes(jax.devices()[0])}")
        check(V >= MIN_UNION_VOCAB, f"union vocabulary {V} < {MIN_UNION_VOCAB}")
        check((n, V_tab, d) == (WORKERS, V, cfg.dim),
              f"stacked tables {(n, V_tab, d)}")
        check(res.timings["steps_per_epoch"] == STEPS,
              f"{res.timings['steps_per_epoch']} steps, wanted {STEPS}")
        check(all(math.isfinite(v) for v in res.losses),
              f"{spec} losses not finite: {res.losses}")
        for method in merges:
            emb, valid = res.merged[method]
            print(f"merge {method}: {res.timings[f'merge_{method}_s']:.2f}s "
                  f"(incl. compile) table {emb.shape} valid rows "
                  f"{int(valid.sum())}")
            check(emb.shape == (V, cfg.dim), f"merged table {emb.shape}")
            check(bool(np.isfinite(emb).all()), "merged table not finite")
        del res


def four_chips(jax, cfg) -> None:
    """8 workers sharded 2 per chip against the same 8 on one chip."""
    import numpy as np

    from repro.core.driver import train_submodels
    from repro.data.corpus import SemanticCorpusModel
    from repro.launch.mesh import multihost_train_kwargs

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")
    corpus = SemanticCorpusModel.create(
        vocab_size=FOUR_RAW_VOCAB, seed=0).generate(FOUR_SENTENCES, seed=1)
    _, sharded_kw = multihost_train_kwargs(FOUR_WORKERS)
    check(sharded_kw.get("backend") == "shard_map",
          f"4 devices did not select shard_map: {sharded_kw}")
    tables = {}
    for label, kw in (("shard_map 4 chips", sharded_kw),
                      ("vmap 1 chip", {"backend": "vmap"})):
        res = train_submodels(
            corpus, FOUR_RAW_VOCAB, "random", FOUR_WORKERS, cfg, epochs=1,
            batch_size=BATCH, max_steps_per_epoch=FOUR_STEPS,
            steps_per_chunk=FOUR_STEPS, base_min_count=FOUR_WORKERS,
            engine="pallas_fused_tiered", seed=0, **kw)
        models = res.stacked.models
        per_dev = sorted(models.sharding.device_set, key=lambda d: d.id)
        print(f"{label}: workers={FOUR_WORKERS} union V="
              f"{res.union_vocab.size} d={cfg.dim} tables on "
              f"{[d.id for d in per_dev]} losses={res.losses!r} "
              f"train_s={res.timings['train_s']:.2f} (incl. compile)")
        check(all(math.isfinite(v) for v in res.losses), f"{label} losses")
        if kw["backend"] == "shard_map":
            check(len(per_dev) == 4, "tables not spread over 4 chips")
            shards = {s.device.id: s.data.shape[0]
                      for s in models.addressable_shards}
            print(f"workers per chip: {shards}")
            check(set(shards.values()) == {FOUR_WORKERS // 4},
                  f"workers per chip {shards}")
        tables[label] = np.asarray(models)
        del res, models
    a, b = tables.values()
    for w in range(FOUR_WORKERS):
        diff = float(np.max(np.abs(a[w] - b[w])))
        print(f"worker {w}: max |4 chips - 1 chip| = {diff!r}")
        check(np.allclose(a[w], b[w], rtol=RTOL, atol=ATOL),
              f"worker {w} disagrees between 4 chips and 1 chip ({diff})")
    for d in devs:
        print(f"device {d.id} peak_bytes={peak_bytes(d)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"the repository's src/ is not beside this script ({SRC})")
    sys.path.insert(0, str(SRC))
    import jax

    facts = device_facts(jax)
    check(facts["platform"] == "tpu",
          f"JAX found no TPU (platform {facts['platform']!r}); this smoke "
          f"run never falls back to the CPU")
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {facts} compile cache: {enable_compile_cache()}")
    cfg = sgns_config()

    if args.four_chips:
        four_chips(jax, cfg)
    else:
        t0 = time.perf_counter()
        check_kernels_against_reference(CHECK_VOCAB, cfg.dim, CHECK_BATCH,
                                        CHECK_BLOCK, cfg.negatives)
        print(f"reference check done in {time.perf_counter() - t0:.1f}s")
        from repro.data.corpus import SemanticCorpusModel

        t0 = time.perf_counter()
        corpus = SemanticCorpusModel.create(
            vocab_size=RAW_VOCAB, seed=0).generate(SENTENCES, seed=1)
        print(f"corpus: {SENTENCES} sentences, {corpus.num_tokens} tokens "
              f"in {time.perf_counter() - t0:.1f}s")
        train_one_chip(corpus, cfg)
    print(f"peak_bytes device 0: {peak_bytes(jax.devices()[0])}")
    print(json.dumps({"ok": True, "device": facts}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
