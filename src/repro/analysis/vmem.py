"""Static VMEM footprint estimation and budget enforcement.

Every Pallas engine config implies a VMEM-resident working set the
Mosaic compiler will demand at lowering time: scratch rings, pinned
table prefixes, whole resident tables, plan operands. On CI (interpret
mode) an over-budget config runs fine and only fails when compiled for
a TPU. For the ``pallas_fused_tiered`` engine the terms are the
kernel's own scratch sizes (``sgns_fused_pipe.vmem_terms``) at the
chip's lane-padded row width; ``tests/test_tpu_compile.py`` compiles
them for a described v5e, which checks the real allocation. This pass
computes the footprint *statically* from the engine dials
``(block_pairs, ring_depth, hot_rows)`` and the model shape
``(V, d, K, B)``, so bad configs are rejected at plan time:

* :class:`repro.core.async_trainer.AsyncShardTrainer` checks dial
  consistency at construction (``engine.validate``);
* ``train_sgns`` / ``dryrun_sgns`` run :func:`check_vmem_budget`
  before training/lowering (``--vmem-budget-mb``);
* ``python -m repro.analysis`` certifies each engine's reference
  operating shape fits the default budget.

The estimate models the terms the kernels actually allocate (scratch
shapes + VMEM-spec operands), not XLA's transient buffers — it is a
lower bound designed to catch the catastrophic misconfigurations
(a deep ring × huge blocks, a hot prefix larger than the budget — at
``hot_rows = V``, VMEM-resident tables past the cliff), with headroom
left to the real compiler.

Standalone: ``python -m repro.analysis.vmem --engine pallas_fused_tiered
--vocab 300000 --dim 500``.
"""

from __future__ import annotations

from dataclasses import dataclass, field

F32 = 4     # bytes; every table/scratch buffer in the stack is f32/i32

# One core's VMEM order (TPU v4/v5e ≈ 16 MiB). A deliberate, documented
# default — override per call/CLI for other parts.
DEFAULT_VMEM_BUDGET_BYTES = 16 * 2 ** 20


class VmemBudgetError(ValueError):
    """An engine config's static VMEM footprint exceeds the budget."""


@dataclass(frozen=True)
class VmemEstimate:
    """Static VMEM working set of one engine config at one shape."""

    engine: str
    shape: dict = field(default_factory=dict)   # V, d, K, B + dials
    terms: dict = field(default_factory=dict)   # name -> bytes

    @property
    def total_bytes(self) -> int:
        return sum(self.terms.values())

    def summary(self) -> str:
        mb = self.total_bytes / 2 ** 20
        parts = ", ".join(f"{k}={v / 2 ** 20:.2f}MiB"
                          for k, v in sorted(self.terms.items(),
                                             key=lambda kv: -kv[1]))
        return (f"{self.engine}: {mb:.2f} MiB VMEM "
                f"({parts or 'no VMEM-resident requirement'})")


def estimate_vmem(engine, *, vocab_size: int, dim: int, negatives: int,
                  batch: int) -> VmemEstimate:
    """Static VMEM footprint of one step of ``engine`` at this shape.

    ``engine`` is an :class:`repro.core.engine.UpdateEngine` instance or
    spec string. Dense/sparse engines have no VMEM-resident requirement
    (XLA manages placement) and estimate to zero.
    """
    from repro.core.engine import get_engine

    eng = get_engine(engine)
    V, d, K, B = vocab_size, dim, negatives, batch
    shape = {"V": V, "d": d, "K": K, "B": B}
    terms: dict[str, int] = {}
    name = eng.name

    if name in ("dense", "sparse"):
        pass
    elif name == "pallas":
        # VMEM-tile row-grad kernel: (blk_b, d) w/cp + (blk_b, K, d) cn
        # tiles in and the same three gradient tiles out; ops.py pads B
        # up to a power of two before picking the tile
        from repro.kernels.sgns_update import _pick_block_b
        Bp = 1 << (max(B, 8) - 1).bit_length()
        bt = eng.block_b or _pick_block_b(Bp, K, d)
        shape["block_b"] = bt
        terms["grad_tiles"] = 2 * bt * (K + 2) * d * F32
    elif name == "pallas_fused_tiered":
        # the kernel's own scratch sizes, at the chip's lane-padded row
        # width (its per-block plan metadata lives in HBM and SMEM)
        from repro.kernels.sgns_fused_pipe import (
            _pick_block_pairs, row_width, vmem_terms)
        blk = _pick_block_pairs(B, eng.block_pairs)
        hot = max(0, min(int(eng.hot_rows), V))
        shape.update(block_pairs=blk, ring_depth=eng.ring_depth, hot_rows=hot)
        terms.update(vmem_terms(blk, K, row_width(d, interpret=False),
                                eng.ring_depth, hot))
    else:   # future engines: unknown ⇒ no static claim
        pass
    return VmemEstimate(eng.describe(), shape, terms)


def check_vmem_budget(engine, *, vocab_size: int, dim: int, negatives: int,
                      batch: int,
                      budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
                      ) -> VmemEstimate:
    """Estimate and enforce: raises :class:`VmemBudgetError` with the
    per-term breakdown and dial advice when the footprint exceeds the
    budget; returns the estimate otherwise."""
    est = estimate_vmem(engine, vocab_size=vocab_size, dim=dim,
                        negatives=negatives, batch=batch)
    if est.total_bytes > budget_bytes:
        advice = ("reduce hot_rows, block_pairs or ring_depth"
                  if est.engine.startswith("pallas_fused_tiered:")
                  else "reduce the blocking dials")
        raise VmemBudgetError(
            f"VMEM budget exceeded: {est.summary()} > "
            f"{budget_bytes / 2 ** 20:.1f} MiB budget — {advice}")
    return est


def main(argv=None) -> int:
    import argparse

    from repro.core.engine import ENGINE_NAMES, get_engine

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--engine", default=None,
                    help="one engine spec (default: every registered "
                         "engine)")
    ap.add_argument("--vocab", type=int, default=300_000)
    ap.add_argument("--dim", type=int, default=500)
    ap.add_argument("--negatives", type=int, default=5)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--hot-rows", type=int, default=None)
    ap.add_argument("--ring-depth", type=int, default=None)
    ap.add_argument("--block-pairs", type=int, default=None)
    ap.add_argument("--budget-mb", type=float, default=16.0,
                    help="0 disables enforcement (report only)")
    args = ap.parse_args(argv)
    overrides = {k: v for k, v in (("hot_rows", args.hot_rows),
                                   ("ring_depth", args.ring_depth),
                                   ("block_pairs", args.block_pairs))
                 if v is not None}
    names = [args.engine] if args.engine else list(ENGINE_NAMES)
    ok = True
    for name in names:
        eng = get_engine(name, **{k: v for k, v in overrides.items()
                                  if hasattr(get_engine(name), k)})
        try:
            if args.budget_mb:
                est = check_vmem_budget(
                    eng, vocab_size=args.vocab, dim=args.dim,
                    negatives=args.negatives, batch=args.batch,
                    budget_bytes=int(args.budget_mb * 2 ** 20))
            else:
                est = estimate_vmem(eng, vocab_size=args.vocab,
                                    dim=args.dim, negatives=args.negatives,
                                    batch=args.batch)
            print(f"vmem: {est.summary()}")
        except VmemBudgetError as e:
            ok = False
            print(f"vmem: REJECTED {e}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
