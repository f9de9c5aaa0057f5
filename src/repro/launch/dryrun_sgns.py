import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ must precede any jax import (see dryrun.py).

"""Dry-run + roofline for the paper's own workload: SGNS word-embedding
training at production scale (vocab 300k, dim 500) on a 256-chip pod.

Cases:
  async          — the paper: 256 sub-models, one per chip, shard_map
                   over the 'worker' axis, `sparse` engine with the
                   inverse-CDF draw. The compiled epoch is asserted to
                   contain ZERO collectives.
  async_alias    — `sparse:alias` engine: the O(1) alias draw replacing
                   the O(log V) CDF binary search. Compare this row's
                   HLO cost against `async` (ROADMAP item 4) — same
                   zero-collective property, less per-step HLO.
  async_fused    — `pallas_fused` engine: the alias draw moves *inside*
                   the step kernel; negative ids and (B,K) logit/grad
                   intermediates never appear as HBM arrays.
  async_fused_hbm— `pallas_fused_hbm` engine: the fused step with the
                   (V, d) tables *HBM-resident* — a grid of pair blocks
                   DMA-gathers/scatters only the touched rows, which is
                   what makes the 300k×500 sub-model shape of this very
                   dry-run feasible per worker. Same zero-collective
                   assertion as every async engine.
  async_fused_pipe— `pallas_fused_pipe` engine: the HBM-resident step
                   with the double-buffered DMA pipeline — deduped row
                   gathers/write-backs on a 2-slot VMEM ring, block
                   b+1's gathers in flight while block b computes,
                   hazard-ordered by the pure-JAX block planner. Same
                   zero-collective assertion (the planner is local
                   sort-and-compare work, no communication).
  async_fused_tiered— `pallas_fused_tiered` engine: the pipelined step
                   with frequency-tiered placement — the hot_rows
                   hottest rows (the frequency-sorted id prefix) pinned
                   VMEM-resident for the whole step, cold rows behind
                   the same DMA ring. Per-worker tables are private, so
                   the hot tier needs no synchronization: the same
                   zero-collective assertion holds.
  sync           — the synchronized strawman (Hogwild/MLLib stand-in):
                   data-parallel minibatch SGNS, dense-gradient psum
                   every step (the 600 MB/step the paper eliminates).
  local_sgd_k    — beyond-paper: parameter averaging every k steps
                   (collective term ∝ 1/k; the paper is k→∞ + ALiR).
  merge          — the one-time ALiR merge phase, sharded over workers
                   (per-model Procrustes local, one all-reduce for Y).

Usage: python -m repro.launch.dryrun_sgns [--json out.json]
       [--cases async,async_alias,...] [--workers N --steps S --batch B]
       [--processes P] [--plan-only]

``--plan-only`` prints the per-host ingestion shard plans and exits
without lowering any case — the cheap multi-host smoke CI runs with
``--processes 4``.
"""

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.sgns_wiki import CONFIG as SGNS_CFG
from repro.core.async_trainer import (
    AsyncShardTrainer, make_sync_epoch, make_periodic_sync_epoch,
    assert_no_collectives)
from repro.core import merge as mg
from repro.launch.mesh import make_worker_mesh
from repro.launch import roofline as rl

WORKERS = 256
STEPS = 128          # steps per lowered epoch (collectives scale linearly)
BATCH = 1024         # pairs per worker per step

ASYNC_ENGINES = {
    "async": "sparse",            # inverse-CDF draw (the PR-1 baseline)
    "async_alias": "sparse:alias",
    "async_pallas": "pallas",
    "async_fused": "pallas_fused",
    "async_fused_hbm": "pallas_fused_hbm",
    "async_fused_pipe": "pallas_fused_pipe",
    "async_fused_tiered": "pallas_fused_tiered",
}


def sds(mesh, shape, dtype, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def lower_async(mesh, workers, steps, batch, engine="sparse"):
    trainer = AsyncShardTrainer(
        cfg=SGNS_CFG, num_workers=workers, total_steps=steps,
        backend="shard_map", mesh=mesh, engine=engine)
    return trainer.lower_epoch(steps, batch)


def lower_sync(mesh, workers, steps, batch):
    neg_cdf = jnp.linspace(0, 1, SGNS_CFG.vocab_size, dtype=jnp.float32)
    epoch = make_sync_epoch(SGNS_CFG, neg_cdf, steps, mesh=mesh,
                            data_axis="worker")
    V, d = SGNS_CFG.vocab_size, SGNS_CFG.dim
    params = {"W": sds(mesh, (V, d), jnp.float32, P()),
              "C": sds(mesh, (V, d), jnp.float32, P())}
    c = sds(mesh, (steps, workers * batch), jnp.int32, P(None, "worker"))
    key = sds(mesh, (2,), jnp.uint32, P())
    step0 = jax.ShapeDtypeStruct((), jnp.int32)
    return epoch.lower(params, c, c, key, step0)


def lower_local_sgd(mesh, workers, steps, batch, k: int):
    neg_cdf = jnp.linspace(0, 1, SGNS_CFG.vocab_size, dtype=jnp.float32)
    epoch = make_periodic_sync_epoch(SGNS_CFG, neg_cdf, steps, k, mesh,
                                     data_axis="worker")
    V, d = SGNS_CFG.vocab_size, SGNS_CFG.dim
    params = {"W": sds(mesh, (V, d), jnp.float32, P()),
              "C": sds(mesh, (V, d), jnp.float32, P())}
    c = sds(mesh, (steps // k, k, workers * batch), jnp.int32,
            P(None, None, "worker"))
    key = sds(mesh, (2,), jnp.uint32, P())
    step0 = jax.ShapeDtypeStruct((), jnp.int32)
    return epoch.lower(params, c, c, key, step0)


def lower_merge(mesh, workers, steps, batch):
    """One ALiR iteration over worker-sharded sub-models."""
    V, d = SGNS_CFG.vocab_size, SGNS_CFG.dim

    def one_iter(models, mask, Y):
        Y_new, disp, _ = mg._alir_iteration(Y, models, mask)
        return Y_new, disp

    models = sds(mesh, (workers, V, d), jnp.float32, P("worker"))
    mask = sds(mesh, (workers, V), jnp.bool_, P("worker"))
    Y = sds(mesh, (V, d), jnp.float32, P())
    return jax.jit(one_iter).lower(models, mask, Y)


def run(case: str, mesh, workers=WORKERS, steps=STEPS, batch=BATCH,
        vmem_budget_mb: float = 0.0) -> dict:
    if case.startswith("local_sgd_"):
        # the lowered program runs whole sync periods only — round the
        # step count so the roofline pairs/model_flops match it
        k = int(case.rsplit("_", 1)[1])
        steps = max(steps // k, 1) * k
    if case in ASYNC_ENGINES:
        # static VMEM footprint at this run's shape: report always,
        # enforce when a budget is given (async_fused is legitimately
        # over-budget at the 300k×500 shape — exactly why the
        # HBM-resident family exists — so the default is report-only)
        from repro.analysis.vmem import check_vmem_budget, estimate_vmem

        if vmem_budget_mb:
            est = check_vmem_budget(
                ASYNC_ENGINES[case], vocab_size=SGNS_CFG.vocab_size,
                dim=SGNS_CFG.dim, negatives=SGNS_CFG.negatives, batch=batch,
                budget_bytes=int(vmem_budget_mb * 2 ** 20))
        else:
            est = estimate_vmem(
                ASYNC_ENGINES[case], vocab_size=SGNS_CFG.vocab_size,
                dim=SGNS_CFG.dim, negatives=SGNS_CFG.negatives, batch=batch)
        print(f"   vmem: {est.summary()}")
        lowered = lower_async(mesh, workers, steps, batch,
                              engine=ASYNC_ENGINES[case])
        # every async engine keeps the paper's headline property —
        # certified by the structured op-walk, not the old HLO regex
        assert_no_collectives(lowered)
    else:
        lowered = {
            "sync": lower_sync,
            "local_sgd_8": lambda m, w, s, b: lower_local_sgd(m, w, s, b, 8),
            "local_sgd_64": lambda m, w, s, b: lower_local_sgd(m, w, s, b, 64),
            "merge_alir_iter": lower_merge,
        }[case](mesh, workers, steps, batch)
    compiled = lowered.compile()
    # model flops: per epoch, 2 tables × (K+1) dots fwd+bwd ≈ 6·B·(K+1)·d
    pairs = workers * batch * steps
    model_flops = 6.0 * pairs * (SGNS_CFG.negatives + 1) * SGNS_CFG.dim
    r = rl.analyze(f"sgns-{case}", f"epoch{steps}", compiled, workers,
                   model_flops=model_flops)
    row = r.row()
    row["collective_ops"] = dict(r.collectives.count_by_op)
    print(f"== sgns/{case}: compute={r.compute_s:.3e}s memory={r.memory_s:.3e}s"
          f" collective={r.collective_s:.3e}s → {r.dominant}"
          f" | collectives={row['collective_ops']}")
    return row


def print_ingestion_plans(workers: int, processes: int, steps: int,
                          batch: int) -> list:
    """Per-host ingestion shard plans for the run's worker count: which
    workers each host extracts and the per-chunk block it contributes to
    `make_array_from_process_local_data`. Pure planning — works for any
    simulated `--processes` on a single-process dry-run."""
    from repro.data.pipeline import HostShardPlan

    plans = HostShardPlan.all_hosts(processes, workers)
    print(f"== ingestion plan: {workers} workers over {processes} host(s)")
    for plan in plans:
        block_mb = plan.num_local * steps * batch * 4 * 2 / 1e6  # c + x int32
        print(f"   {plan.describe()} — chunk block "
              f"({plan.num_local}, {steps}, {batch}) ×2 int32 "
              f"= {block_mb:.1f} MB/chunk")
    owned = sorted(w for p in plans for w in p.workers)
    assert owned == list(range(workers)), "plans must cover each worker once"
    return plans


def compare_sampler_paths(rows: list[dict]) -> None:
    """ROADMAP item 4: alias vs CDF negative-draw HLO cost, side by side.
    Both async rows are collective-free by assertion, so the comparison
    is purely the per-chip compute/memory roofline terms."""
    by_case = {r["arch"]: r for r in rows}
    base = by_case.get("sgns-async")
    for other in ("sgns-async_alias", "sgns-async_fused",
                  "sgns-async_fused_hbm", "sgns-async_fused_pipe",
                  "sgns-async_fused_tiered"):
        r = by_case.get(other)
        if not (base and r):
            continue
        dc = r["compute_s"] / max(base["compute_s"], 1e-30)
        dm = r["memory_s"] / max(base["memory_s"], 1e-30)
        print(f"-- {other[5:]} vs async (cdf draw): "
              f"compute ×{dc:.3f}, memory ×{dm:.3f} "
              f"(both zero-collective)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--cases",
                    default="async,async_alias,sync,local_sgd_8,"
                            "local_sgd_64,merge_alir_iter",
                    help="comma list; also available: async_pallas, "
                         "async_fused, async_fused_hbm, async_fused_pipe, "
                         "async_fused_tiered")
    ap.add_argument("--workers", type=int, default=WORKERS)
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--processes", type=int, default=None,
                    help="ingestion hosts to plan for (default: "
                         "jax.process_count(); any count can be simulated)")
    ap.add_argument("--plan-only", action="store_true",
                    help="print the per-host ingestion plans and exit "
                         "(no case lowering — the CI multi-host smoke)")
    ap.add_argument("--vmem-budget-mb", type=float, default=0.0,
                    help="reject async cases whose static VMEM estimate "
                         "exceeds this budget (0 = report only; "
                         "async_fused at the 300k×500 dry-run shape is "
                         "over any realistic budget by design)")
    args = ap.parse_args(argv)
    processes = (args.processes if args.processes is not None
                 else jax.process_count())
    plans = print_ingestion_plans(args.workers, processes, args.steps,
                                  args.batch)
    if args.plan_only:
        assert plans, "ingestion planning produced no per-host plans"
        return
    # one simulated device per worker
    mesh = make_worker_mesh(args.workers, jax.devices()[:args.workers])
    rows = [run(c, mesh, args.workers, args.steps, args.batch,
                vmem_budget_mb=args.vmem_budget_mb)
            for c in args.cases.split(",")]
    compare_sampler_paths(rows)
    if args.json:
        existing = json.load(open(args.json)) if os.path.exists(args.json) else []
        json.dump(existing + rows, open(args.json, "w"), indent=1)


if __name__ == "__main__":
    main()
