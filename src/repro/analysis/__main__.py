"""Run every static-analysis pass — the CI ``static-analysis`` job.

``python -m repro.analysis`` executes, in order:

1. **dma-model** — exhaustive model check of the pipeline DMA schedule
   (ring_depth ∈ {2,3,4} × all hazard vectors × padded tails) plus the
   planner integration sweep.
2. **contracts** — zero-collective + table-donation-aliasing
   certification for every registered engine, and the ``@zipf50k``
   planner-traffic ↔ bench-baseline cross-check.
3. **vmem** — each engine fits the default 16 MiB budget at the paper
   shape, and the known-over-budget config (the fused engine with the
   whole table as its hot prefix) is rejected.
4. **lint** — the repo-specific AST rules over ``src/repro``.

``--quick`` shrinks the dma-model bounds for fast local iteration;
CI runs the full bounds. Exit status is nonzero if any pass fails.
"""

from __future__ import annotations

import argparse
import sys
import time


def _run_dma_model(quick: bool) -> bool:
    from repro.analysis import dma_model

    report = dma_model.run(
        max_nblocks_schedule=4 if quick else 6,
        max_nblocks_planner=3 if quick else 4)
    print(f"dma_model: {report.summary()}")
    return report.ok


def _run_contracts(baseline: str) -> bool:
    from repro.analysis import contracts

    return contracts.main(["--baseline", baseline]) == 0


# The reference operating point every engine must fit in
# DEFAULT_VMEM_BUDGET_BYTES: the paper shape, V=300k × d=500.
_PAPER = dict(vocab_size=300_000, dim=500, negatives=5, batch=1024)
_VMEM_REFERENCE = ["dense", "sparse", "pallas", "pallas_fused_tiered:alias"]


def _run_vmem() -> bool:
    from repro.analysis.vmem import VmemBudgetError, check_vmem_budget
    from repro.core.engine import get_engine

    ok = True
    for spec in _VMEM_REFERENCE:
        try:
            est = check_vmem_budget(spec, **_PAPER)
            print(f"vmem: {est.summary()} ✓")
        except VmemBudgetError as e:
            ok = False
            print(f"vmem: FAILED {e}")
    # The cliff itself must still be caught: VMEM-resident tables at
    # the paper shape (a hot prefix of all V rows) have to be rejected,
    # not waved through.
    resident = get_engine("pallas_fused_tiered",
                          hot_rows=_PAPER["vocab_size"])
    try:
        check_vmem_budget(resident, **_PAPER)
        ok = False
        print("vmem: FAILED pallas_fused_tiered at hot_rows=V was NOT "
              "rejected — the estimator lost the VMEM cliff")
    except VmemBudgetError:
        print("vmem: pallas_fused_tiered at hot_rows=V correctly "
              "rejected ✓")
    return ok


def _run_lint() -> bool:
    from repro.analysis import lint_rules

    return lint_rules.main(["src/repro"]) == 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="reduced dma-model bounds for local iteration")
    ap.add_argument("--skip", action="append", default=[],
                    choices=["dma-model", "contracts", "vmem", "lint"],
                    help="skip a pass (repeatable)")
    ap.add_argument("--baseline", default="BENCH_wallclock.json",
                    help="bench baseline for the traffic cross-check")
    args = ap.parse_args(argv)

    passes = [
        ("dma-model", lambda: _run_dma_model(args.quick)),
        ("contracts", lambda: _run_contracts(args.baseline)),
        ("vmem", _run_vmem),
        ("lint", _run_lint),
    ]
    failed = []
    for name, fn in passes:
        if name in args.skip:
            print(f"== {name}: skipped ==")
            continue
        print(f"== {name} ==")
        t0 = time.perf_counter()
        ok = fn()
        dt = time.perf_counter() - t0
        print(f"== {name}: {'OK' if ok else 'FAILED'} ({dt:.1f}s) ==")
        if not ok:
            failed.append(name)
    if failed:
        print(f"static analysis FAILED: {', '.join(failed)}")
        return 1
    print("static analysis: all passes OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
