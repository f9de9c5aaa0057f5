"""The paper's training driver: divide → async train → merge → evaluate.

  PYTHONPATH=src python -m repro.launch.train_sgns \
      --strategy shuffle --workers 10 --epochs 6 --dim 64 \
      --sentences 30000 --merge alir_pca concat pca

Runs the full pipeline on the synthetic corpus (see DESIGN.md §4) and
prints paper-style scores + timings. ``--engine`` selects the per-step
update engine (``sparse``, ``dense``, ``pallas``, ``pallas_fused``,
``pallas_fused_hbm``, ``pallas_fused_pipe``, ``pallas_fused_tiered``,
optionally with a sampler suffix like ``sparse:alias``); Pallas engines
run in interpret mode on CPU. On a TPU, ``pallas``, ``pallas_fused_pipe``
and ``pallas_fused_tiered`` compile under Mosaic, while ``pallas_fused``
and ``pallas_fused_hbm`` raise (no Mosaic lowering).
``pallas_fused_hbm`` keeps the parameter tables HBM-resident and
DMA-streams only the touched rows per pair block — the serial oracle
for paper-scale (300k×500) sub-models; ``pallas_fused_pipe`` is its
double-buffered successor
(deduped row DMAs overlapped with compute behind a hazard-ordering
block planner), and ``pallas_fused_tiered`` adds frequency-tiered
placement on top (``--hot-rows`` hottest rows pinned VMEM-resident,
cold rows behind a ``--ring-depth``-slot DMA ring).

``--graph edges.npz`` trains node embeddings instead (DeepWalk): each
worker's RANDOM SAMPLING (``--rate``) of the 80 walk starts per node
streams walks of 40 nodes, whose pairs train its sub-model; the merged
table's rows are the graph's nodes in descending degree order.

  PYTHONPATH=src python -m repro.data.graph --out /tmp/edges.npz
  PYTHONPATH=src python -m repro.launch.train_sgns --graph /tmp/edges.npz \
      --workers 4 --rate 0.02 --epochs 1 --dim 64 --merge alir_pca
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core.driver import (apply_merges, prepare_graph_training,
                               run_pipeline, train_prepared,
                               train_sync_baseline)
from repro.core.engine import get_engine
from repro.core.sgns import SGNSConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import multihost_train_kwargs
from repro.data.corpus import SemanticCorpusModel
from repro.data.graph import Graph
from repro.eval.benchmarks import BenchmarkSuite, evaluate_all
from repro.checkpoint import save_checkpoint


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="shuffle",
                    choices=("equal", "random", "shuffle"))
    ap.add_argument("--workers", type=int, default=10)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--window", type=int, default=5)
    ap.add_argument("--negatives", type=int, default=5)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=2000)
    ap.add_argument("--sentences", type=int, default=30000)
    ap.add_argument("--merge", nargs="+",
                    default=("concat", "pca", "alir_pca"),
                    help="merge methods to apply (see "
                         "repro.core.merge.MERGE_METHODS; alir_tree is "
                         "the log-depth reduction-tree merge)")
    ap.add_argument("--merge-fan-in", type=int, default=2,
                    help="reduction-tree arity for the alir_tree merge "
                         "(>= 2; depth = ceil(log_fan_in(workers)))")
    ap.add_argument("--merge-shard", type=int, default=1,
                    help="ALiR Gram-accumulation row-block count — a "
                         "static dial: bits depend on the count, never "
                         "on which host computes which block")
    ap.add_argument("--baseline", action="store_true",
                    help="also train the synchronized baseline")
    ap.add_argument("--engine", default="sparse",
                    help="update engine: dense | sparse | pallas | "
                         "pallas_fused | pallas_fused_hbm | "
                         "pallas_fused_pipe | pallas_fused_tiered, "
                         "optionally ':cdf'/':alias' (e.g. sparse:alias)")
    ap.add_argument("--hot-rows", type=int, default=None,
                    help="pallas_fused_tiered: rows of the frequency-"
                         "sorted id prefix pinned VMEM-resident per "
                         "table (default 256; 0 = pure pipeline)")
    ap.add_argument("--ring-depth", type=int, default=None,
                    help="pallas_fused_pipe/_tiered: VMEM row-buffer "
                         "ring slots for the cold-row DMA pipeline "
                         "(default 2)")
    ap.add_argument("--processes", type=int, default=None,
                    help="ingestion host count (default: "
                         "jax.process_count()); each host extracts only "
                         "its HostShardPlan block of worker streams")
    ap.add_argument("--process-index", type=int, default=None,
                    help="this host's index (default: jax.process_index())")
    ap.add_argument("--vmem-budget-mb", type=float, default=16.0,
                    help="reject engine configs whose static VMEM "
                         "estimate (repro.analysis.vmem) exceeds this "
                         "budget before training starts (0 = report "
                         "only; default one TPU core's 16 MiB)")
    ap.add_argument("--elastic-state", default=None, metavar="DIR",
                    help="train preemption-tolerantly: each worker "
                         "checkpoints (tables + cursor) to DIR and a "
                         "re-run of the same command resumes every "
                         "worker from its last checkpoint, bit-identical "
                         "to the uninterrupted elastic run "
                         "(single-process; see docs/ARCHITECTURE.md "
                         "§Elasticity)")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="elastic checkpoint cadence in chunks, anchored "
                         "to stream position (default 1)")
    ap.add_argument("--no-resume", action="store_true",
                    help="with --elastic-state: ignore existing "
                         "checkpoints and train from scratch")
    ap.add_argument("--graph", default=None, metavar="EDGES_NPZ",
                    help="train DeepWalk node embeddings on this edge "
                         "list (src, dst, num_nodes; `python -m "
                         "repro.data.graph` writes one) instead of the "
                         "synthetic corpus")
    ap.add_argument("--save", default=None, help="checkpoint path (.npz)")
    ap.add_argument("--publish", default=None, metavar="DIR",
                    help="incrementally ALiR-fold the sub-models and "
                         "publish versioned merged-table artifacts to "
                         "DIR (serve with `python -m repro.launch.serve "
                         "--artifact DIR`)")
    ap.add_argument("--publish-every", type=int, default=1,
                    help="publish a table version every k folded "
                         "sub-models (default 1: a version per worker)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # engine-dial overrides only when set: passing hot_rows/ring_depth
    # to an engine without those fields is a clear TypeError
    overrides = {k: v for k, v in (("hot_rows", args.hot_rows),
                                   ("ring_depth", args.ring_depth))
                 if v is not None}
    args.engine = get_engine(args.engine, **overrides)
    # fail fast on a config that would blow the VMEM budget at this
    # run's shape, before any corpus generation or training happens
    from repro.analysis.vmem import check_vmem_budget, estimate_vmem
    if args.vmem_budget_mb:
        est = check_vmem_budget(
            args.engine, vocab_size=args.vocab, dim=args.dim,
            negatives=args.negatives, batch=args.batch,
            budget_bytes=int(args.vmem_budget_mb * 2 ** 20))
    else:
        est = estimate_vmem(args.engine, vocab_size=args.vocab,
                            dim=args.dim, negatives=args.negatives,
                            batch=args.batch)
    print(f"vmem: {est.summary()}")
    processes, train_kw = multihost_train_kwargs(args.workers, args.processes)
    if args.graph:
        return train_graph(args, processes, train_kw)

    gen = SemanticCorpusModel.create(vocab_size=args.vocab, seed=0)
    corpus = gen.generate(num_sentences=args.sentences, seed=1)
    suite = BenchmarkSuite.from_model(gen, top_words=int(args.vocab * 0.6))
    cfg = SGNSConfig(vocab_size=0, dim=args.dim, window=args.window,
                     negatives=args.negatives)

    if args.elastic_state:
        from repro.elastic import train_submodels_elastic

        res = train_submodels_elastic(
            corpus, args.vocab, args.strategy, args.workers, cfg,
            state_dir=args.elastic_state, resume=not args.no_resume,
            ckpt_every=args.ckpt_every, epochs=args.epochs,
            batch_size=args.batch, rate=args.rate, window=args.window,
            max_vocab=None, base_min_count=20, engine=args.engine)
        res = apply_merges(res, tuple(args.merge), out_dim=cfg.dim,
                           fan_in=args.merge_fan_in, shard=args.merge_shard)
    else:
        res = run_pipeline(
            corpus, args.vocab, strategy=args.strategy,
            num_workers=args.workers, cfg=cfg, epochs=args.epochs,
            batch_size=args.batch, rate=args.rate,
            window=args.window, max_vocab=None, base_min_count=20,
            merge_methods=tuple(args.merge),
            merge_fan_in=args.merge_fan_in, merge_shard=args.merge_shard,
            engine=args.engine,
            process_index=args.process_index, process_count=processes,
            **train_kw)
    print(f"strategy={args.strategy} workers={args.workers} "
          f"engine={args.engine.describe()} "
          f"train={res.timings['train_s']:.1f}s "
          f"steps/epoch={res.timings['steps_per_epoch']} "
          f"losses={['%.3f' % l for l in res.losses]}")
    for m, (emb, valid) in res.merged.items():
        scores = evaluate_all(emb, valid, res.union_vocab, suite)
        print(f"  {m:10s} sim={scores['similarity']:.3f}"
              f"({scores['similarity_oov']}) "
              f"ana={scores['analogy']:.3f}({scores['analogy_oov']}) "
              f"cat={scores['categorization']:.3f}"
              f"({scores['categorization_oov']}) "
              f"merge={res.timings.get('merge_%s_s' % m, 0):.2f}s")

    if args.baseline:
        params, vocab, info = train_sync_baseline(
            corpus, args.vocab, cfg, epochs=args.epochs,
            batch_size=args.batch, window=args.window, max_vocab=None)
        emb = np.asarray(params["W"])
        scores = evaluate_all(emb, np.ones(vocab.size, bool), vocab, suite)
        print(f"  sync-base  sim={scores['similarity']:.3f} "
              f"ana={scores['analogy']:.3f} "
              f"cat={scores['categorization']:.3f} "
              f"train={info['train_s']:.1f}s")

    if args.publish:
        from repro.serve import publish_incremental
        from repro.serve.publish import submodel_arrivals
        versions, final = publish_incremental(
            submodel_arrivals(res.stacked), args.publish,
            word_ids=res.union_vocab.word_ids,
            publish_every=args.publish_every,
            meta={"strategy": args.strategy})
        print(f"published {len(versions)} incremental table version(s) → "
              f"{args.publish} (latest v{versions[-1]}, "
              f"{int(np.asarray(final.valid).sum())} rows valid); serve: "
              f"python -m repro.launch.serve --artifact {args.publish} "
              f"--query <ids>")

    if args.save:
        save_merged(res, args)


def save_merged(res, args):
    best = args.merge[-1]
    emb, valid = res.merged[best]
    save_checkpoint(args.save, {"embedding": emb, "valid": valid,
                                "word_ids": res.union_vocab.word_ids},
                    extra={"method": best, "strategy": args.strategy})
    print(f"saved merged embedding → {args.save}")


def train_graph(args, processes, train_kw):
    """``--graph``: DeepWalk through the same trainer and merge."""
    if args.elastic_state or args.baseline or args.publish:
        raise SystemExit("--graph runs without --elastic-state, "
                         "--baseline and --publish")
    graph = Graph.load(args.graph)
    cfg = SGNSConfig(vocab_size=0, dim=args.dim, window=args.window,
                     negatives=args.negatives)
    setup = prepare_graph_training(
        graph, args.workers, cfg, epochs=args.epochs, batch_size=args.batch,
        rate=args.rate, engine=args.engine,
        process_index=args.process_index, process_count=processes)
    res = train_prepared(setup, **train_kw)
    res = apply_merges(res, tuple(args.merge), out_dim=cfg.dim,
                       fan_in=args.merge_fan_in, shard=args.merge_shard)
    print(f"graph {graph.num_nodes} nodes {graph.num_edges} edges "
          f"workers={args.workers} engine={args.engine.describe()} "
          f"train={res.timings['train_s']:.1f}s "
          f"steps/epoch={res.timings['steps_per_epoch']} "
          f"losses={['%.3f' % l for l in res.losses]}")
    for m, (emb, _) in res.merged.items():
        print(f"  {m:10s} table {emb.shape} "
              f"merge={res.timings.get('merge_%s_s' % m, 0):.2f}s")
    if args.save:
        save_merged(res, args)
    return res


if __name__ == "__main__":
    main()
