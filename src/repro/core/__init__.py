"""Core — the paper's contribution: divide / train / merge.

* :mod:`repro.core.sampling`       — EQUAL PARTITIONING / RANDOM SAMPLING / SHUFFLE
* :mod:`repro.core.sgns`           — SGNS objective + dense/sparse steps
* :mod:`repro.core.engine`         — UpdateEngine registry (dense/sparse/pallas/pallas_fused_tiered)
* :mod:`repro.core.schedule`       — epoch/chunk/total-steps derivation
* :mod:`repro.core.async_trainer`  — zero-collective async training + sync baseline
* :mod:`repro.core.merge`          — Concat / PCA / ALiR (+ OOV reconstruction)
* :mod:`repro.core.distributions`  — unigram/bigram KL tooling (Fig. 1, Thm 2)
"""

from repro.core.sgns import SGNSConfig, init_params, loss_fn, embedding_matrix
from repro.core.sampling import sample_sentence_indices, STRATEGIES
from repro.core.engine import UpdateEngine, get_engine, ENGINE_NAMES
from repro.core.schedule import EpochSchedule, plan_epoch
from repro.core.async_trainer import (
    AsyncShardTrainer,
    make_sync_epoch,
    assert_no_collectives,
    count_collective_ops,
)
from repro.core.merge import (
    StackedModels,
    stack_models,
    merge as merge_embeddings,  # `repro.core.merge` stays the submodule
    Merger,
    MergeConfig,
    MergeResult,
    get_merger,
    MERGER_NAMES,
    merge_alir,      # deprecated shims — the registry is the surface
    merge_concat,
    merge_pca,
    merge_average,
    orthogonal_procrustes,
    reconstruct_missing,
    MERGE_METHODS,
)

__all__ = [
    "SGNSConfig", "init_params", "loss_fn", "embedding_matrix",
    "sample_sentence_indices", "STRATEGIES",
    "UpdateEngine", "get_engine", "ENGINE_NAMES",
    "EpochSchedule", "plan_epoch",
    "AsyncShardTrainer", "make_sync_epoch", "assert_no_collectives",
    "count_collective_ops",
    "StackedModels", "stack_models", "merge_embeddings",
    "Merger", "MergeConfig", "MergeResult", "get_merger", "MERGER_NAMES",
    "merge_alir", "merge_concat",
    "merge_pca", "merge_average", "orthogonal_procrustes",
    "reconstruct_missing", "MERGE_METHODS",
]
