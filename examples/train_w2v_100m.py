"""End-to-end driver at the paper's model scale: a ~100M-parameter SGNS
model (vocab 100k × dim 500 input table, matching the paper's 300k×500
setup proportions) trained for a few hundred steps per async worker,
merged with ALiR, evaluated, checkpointed.

    PYTHONPATH=src python examples/train_w2v_100m.py [--steps 600]

This is the paper's kind of workload (embedding *training*), so the
end-to-end example trains rather than serves. ~10-15 min on CPU
at the defaults; pass smaller --steps/--vocab for a quick pass.

Ingestion is the streaming pipeline: pairs are extracted block-of-
sentences at a time into fixed-shape chunks and prefetched to the device
while it trains — no epoch of pairs is ever materialized in host memory.
The per-step compute is an update engine (``--engine``): the default
``sparse:alias`` draws negatives from the O(1) alias sampler;
``pallas_fused_tiered`` runs the whole step in one kernel with the
(V, d) tables HBM-resident — the engine sized for exactly this
example's 100k×500 (and the paper's 300k×500) tables: each touched row
deduped to one DMA per pair block, gathers overlapped with compute
behind a hazard-ordering planner on a ``--ring-depth``-slot ring, and
the ``--hot-rows`` hottest rows (the frequency-sorted id prefix) pinned
VMEM-resident so the Zipfian bulk of row traffic never touches DMA (0
is the pure-DMA case); ``sparse:cdf`` is the binary-search oracle.
"""

import argparse
import time

import numpy as np

from repro.core.driver import train_submodels
from repro.core.merge import merge as merge_models
from repro.core.sgns import SGNSConfig
from repro.data.corpus import SemanticCorpusModel
from repro.eval.benchmarks import BenchmarkSuite, evaluate_all
from repro.checkpoint import save_checkpoint


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600,
                    help="steps per worker per epoch")
    ap.add_argument("--vocab", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=500)
    ap.add_argument("--workers", type=int, default=10)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--engine", default="sparse:alias",
                    help="update engine (dense | sparse | pallas | "
                         "pallas_fused_tiered, optional ':cdf'/':alias' "
                         "suffix)")
    ap.add_argument("--hot-rows", type=int, default=None,
                    help="pallas_fused_tiered: VMEM-pinned hot-prefix "
                         "rows per table (default 256)")
    ap.add_argument("--ring-depth", type=int, default=None,
                    help="pallas_fused_tiered: cold-row DMA ring "
                         "slots (default 2)")
    ap.add_argument("--steps-per-chunk", type=int, default=128,
                    help="steps per fixed-shape streamed chunk")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="chunk prefetch depth (host/device overlap)")
    ap.add_argument("--processes", type=int, default=None,
                    help="ingestion host count (default: "
                         "jax.process_count()); >1 shards the worker "
                         "streams per host and trains under shard_map")
    ap.add_argument("--process-index", type=int, default=None,
                    help="this host's index (default: jax.process_index())")
    ap.add_argument("--save", default="/tmp/w2v_100m.npz")
    args = ap.parse_args()

    from repro.core.engine import get_engine
    from repro.launch.mesh import multihost_train_kwargs
    overrides = {k: v for k, v in (("hot_rows", args.hot_rows),
                                   ("ring_depth", args.ring_depth))
                 if v is not None}
    args.engine = get_engine(args.engine, **overrides)
    processes, train_kw = multihost_train_kwargs(args.workers, args.processes)

    print(f"model: 2 × {args.vocab} × {args.dim} = "
          f"{2*args.vocab*args.dim/1e6:.0f}M parameters")
    gen = SemanticCorpusModel.create(vocab_size=args.vocab, num_topics=64,
                                     seed=0)
    corpus = gen.generate(num_sentences=120_000, seed=1)
    print(f"corpus: {corpus.num_sentences} sentences, "
          f"{corpus.num_tokens/1e6:.1f}M tokens")
    suite = BenchmarkSuite.from_model(gen, top_words=min(20_000, args.vocab))

    cfg = SGNSConfig(vocab_size=0, dim=args.dim, window=5, negatives=5)
    t0 = time.perf_counter()
    res = train_submodels(
        corpus, args.vocab, strategy="shuffle", num_workers=args.workers,
        cfg=cfg, epochs=args.epochs, batch_size=1024, window=5,
        max_vocab=args.vocab, base_min_count=10,
        max_steps_per_epoch=args.steps, engine=args.engine,
        steps_per_chunk=args.steps_per_chunk, prefetch=args.prefetch,
        process_index=args.process_index, process_count=processes,
        **train_kw)
    print(f"async training: {res.timings['train_s']:.1f}s total "
          f"({res.timings['train_s']/args.workers:.1f}s/worker projected "
          f"parallel), losses {['%.3f' % l for l in res.losses]}")

    t0 = time.perf_counter()
    emb, valid = merge_models(res.stacked, "alir_pca", out_dim=args.dim)
    emb = np.asarray(emb)
    print(f"ALiR merge of {args.workers} × ({res.union_vocab.size}, "
          f"{args.dim}) sub-models: {time.perf_counter()-t0:.1f}s")

    scores = evaluate_all(emb, np.asarray(valid), res.union_vocab, suite)
    print(f"merged model: sim ρ={scores['similarity']:.3f} "
          f"analogy={scores['analogy']:.3f} "
          f"purity={scores['categorization']:.3f}")
    save_checkpoint(args.save, {"embedding": emb,
                                "word_ids": res.union_vocab.word_ids})
    print(f"checkpoint → {args.save}")


if __name__ == "__main__":
    main()
