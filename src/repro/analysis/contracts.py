"""HLO/StableHLO contract certifier for the registered engines.

The paper's headline property — zero parameter synchronization — was
previously asserted by a regex over ``lowered.as_text()`` that matched
the *post-compile* HLO spellings (``all-reduce``). Lowered text on
current jax is StableHLO MLIR, where collectives print as
``stablehlo.all_reduce`` — the regex was vacuous there (a planted
``psum`` sailed through). This module replaces it with a structured
walk over the program text as *ops*:

* :func:`certify_zero_collective` — parses each line's **op position**
  (MLIR ``dialect.op`` after the optional ``%... =`` results, or HLO
  opcode immediately before its operand list) in both formats, so a
  collective spelled either way is caught and a collective name inside
  a metadata/location string is not a false positive.
* :func:`certify_table_aliasing` — lowers each engine's step with the
  parameter pytree donated and certifies both ``(V, d)`` tables carry
  ``tf.aliasing_output`` input/output aliasing — i.e. the update is
  genuinely in place, no silent full-table copy per step.
* :func:`certify_bench_traffic` — recomputes the ``@zipf50k``
  planner-predicted HBM row traffic from the shared workload
  definition (``repro.analysis.workloads``) and certifies it equals
  the committed ``BENCH_wallclock.json`` baseline the CI bench gate
  compares against.

``repro.core.async_trainer.assert_no_collectives`` and
``count_collective_ops`` delegate here, as do the ``dryrun_sgns``
cases and the engine×sampler test matrix — one checker, no duplicated
regexes.

Standalone: ``python -m repro.analysis.contracts`` certifies every
registered engine.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass

# Cross-device communication primitives in both surface syntaxes.
# MLIR dialects (lowered text): stablehlo / mhlo, underscore spellings.
_MLIR_COLLECTIVES = {
    "all_reduce", "all_gather", "all_to_all", "collective_permute",
    "reduce_scatter", "collective_broadcast",
}
# Post-compile HLO: hyphen spellings, plus async -start/-done forms.
_HLO_COLLECTIVES = {
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast",
}

# MLIR op position: optional `%r = ` / `%r:2 = ` result list, then a
# (possibly quoted, generic-form) `dialect.op`. Attribute continuation
# lines, type annotations and loc("...") strings never match: they do
# not start with an identifier immediately followed by a dot.
_MLIR_OP_RE = re.compile(
    r"^\s*(?:%[\w.#$:]+(?:\s*,\s*%[\w.#$:]+)*\s*=\s*)?"
    r"\"?([a-z_][\w$]*)\.([a-z_][\w]*)\"?(?=[\s(\"])")
# HLO op position: `%name = <shape> opcode(` — the opcode is the
# identifier immediately before the operand '(' (metadata strings sit
# after the operand list and are never the first such identifier).
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?[%\w.-]+\s*=\s*.*?\b([a-z][a-z0-9-]*)\(")


class ContractViolation(AssertionError):
    """A certified contract does not hold. Subclasses AssertionError so
    existing callers of the old assertion helpers keep working."""


def _as_text(lowered_or_text) -> str:
    if isinstance(lowered_or_text, str):
        return lowered_or_text
    return lowered_or_text.as_text()


def parse_op_counts(text: str) -> dict[str, int]:
    """Ops by name at op position, both formats merged: MLIR ops as
    ``dialect.op``, HLO opcodes bare."""
    out: dict[str, int] = {}
    for line in text.splitlines():
        m = _MLIR_OP_RE.match(line)
        if m:
            name = f"{m.group(1)}.{m.group(2)}"
            out[name] = out.get(name, 0) + 1
            continue
        m = _HLO_OP_RE.match(line)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0) + 1
    return out


def _is_collective(op: str) -> bool:
    if "." in op:       # MLIR dialect.op
        return op.split(".", 1)[1] in _MLIR_COLLECTIVES
    base = re.sub(r"-(start|done)$", "", op)
    return base in _HLO_COLLECTIVES


def count_collective_ops(text: str) -> dict[str, int]:
    """Collective ops (either format) by name — structured op-position
    parse, immune to the metadata-string false positives and the
    MLIR-spelling false negatives of the old regex."""
    return {op: n for op, n in parse_op_counts(text).items()
            if _is_collective(op)}


def certify_zero_collective(lowered_or_text, label: str = "") -> str:
    """Certify a lowered/compiled program contains zero cross-device
    collectives; returns the text. Accepts a ``Lowered``/``Compiled``
    object (``.as_text()``) or raw program text in either format."""
    txt = _as_text(lowered_or_text)
    hits = count_collective_ops(txt)
    if hits:
        where = f" [{label}]" if label else ""
        raise ContractViolation(
            f"zero-collective contract violated{where}: found "
            f"{dict(sorted(hits.items()))}")
    return txt


# ---------------------------------------------------------------------------
# Donation aliasing of the (V, d) tables.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class AliasingReport:
    engine: str
    vocab_size: int
    dim: int
    aliased_table_args: int     # (V, d) f32 args carrying tf.aliasing_output
    expected: int = 2           # W and C


def _step_arg_structs(engine, cfg, batch: int):
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    V, d = cfg.vocab_size, cfg.dim
    params = {"W": sds((V, d), jnp.float32), "C": sds((V, d), jnp.float32)}
    if engine.table_kind == "alias":
        table = {"prob": sds((V,), jnp.float32),
                 "alias": sds((V,), jnp.int32)}
    else:
        table = sds((V,), jnp.float32)
    return (params, sds((batch,), jnp.int32), sds((batch,), jnp.int32),
            table, sds((2,), jnp.uint32), sds((), jnp.int32))


def certify_table_aliasing(engine_spec, *, vocab_size: int = 150,
                           dim: int = 32, negatives: int = 4,
                           batch: int = 64,
                           total_steps: int = 100) -> AliasingReport:
    """Lower one engine step with the parameter pytree donated and
    certify both ``(V, d)`` tables are input/output-aliased
    (``tf.aliasing_output`` on their args) — the update really is in
    place; a step that silently copies the tables fails here."""
    import jax

    from repro.core.engine import get_engine
    from repro.core.sgns import SGNSConfig

    engine = get_engine(engine_spec)
    cfg = SGNSConfig(vocab_size=vocab_size, dim=dim, negatives=negatives)
    step = engine.make_step(cfg, total_steps=total_steps)
    lowered = jax.jit(step, donate_argnums=(0,)).lower(
        *_step_arg_structs(engine, cfg, batch))
    txt = lowered.as_text()
    table_arg = re.compile(
        rf"tensor<{vocab_size}x{dim}xf32>\s*\{{[^}}]*tf\.aliasing_output")
    rep = AliasingReport(engine.describe(), vocab_size, dim,
                         aliased_table_args=len(table_arg.findall(txt)))
    if rep.aliased_table_args < rep.expected:
        raise ContractViolation(
            f"table-aliasing contract violated [{rep.engine}]: only "
            f"{rep.aliased_table_args}/{rep.expected} (V, d) table args "
            f"carry tf.aliasing_output — the donated tables are being "
            f"silently copied each step")
    return rep


# ---------------------------------------------------------------------------
# Whole-engine certification (epoch-level zero-collective + aliasing).
# ---------------------------------------------------------------------------
def lower_engine_epoch(engine_spec, *, vocab_size: int = 150, dim: int = 32,
                       negatives: int = 4, steps: int = 4, batch: int = 64):
    """Lower a 1-worker ``shard_map`` epoch for an engine spec — the
    same path the dryrun and the production mesh use."""
    import jax

    from repro.core.async_trainer import AsyncShardTrainer
    from repro.core.sgns import SGNSConfig

    mesh = jax.make_mesh((1,), ("worker",))
    tr = AsyncShardTrainer(
        cfg=SGNSConfig(vocab_size=vocab_size, dim=dim, negatives=negatives),
        num_workers=1, total_steps=steps, backend="shard_map", mesh=mesh,
        engine=engine_spec)
    return tr.lower_epoch(steps=steps, batch=batch)


@dataclass(frozen=True)
class EngineContractReport:
    engine: str
    zero_collective: bool
    aliasing: AliasingReport


def certify_engine_contracts(engine_spec, *, vocab_size: int = 150,
                             dim: int = 32, negatives: int = 4,
                             steps: int = 4,
                             batch: int = 64) -> EngineContractReport:
    """Zero-collective (epoch under shard_map) + table aliasing (donated
    step) for one engine spec. Raises :class:`ContractViolation`."""
    from repro.core.engine import get_engine

    engine = get_engine(engine_spec)
    certify_zero_collective(
        lower_engine_epoch(engine, vocab_size=vocab_size, dim=dim,
                           negatives=negatives, steps=steps, batch=batch),
        label=f"{engine.describe()} epoch")
    rep = certify_table_aliasing(engine, vocab_size=vocab_size, dim=dim,
                                 negatives=negatives, batch=batch)
    return EngineContractReport(engine.describe(), True, rep)


# ---------------------------------------------------------------------------
# Planner-predicted DMA traffic vs the committed bench baseline.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TrafficReport:
    engine: str
    predicted_rows: int
    baseline_rows: int


def certify_bench_traffic(
        baseline_path: str = "BENCH_wallclock.json") -> list[TrafficReport]:
    """Recompute the ``@zipf50k`` per-step HBM row traffic from the
    shared workload definition and certify it matches the committed
    baseline rows the CI bench gate compares against — the planner and
    the gated numbers cannot drift apart silently."""
    from repro.analysis.workloads import ZIPF50K_ROWS, zipf50k_row_traffic

    with open(baseline_path) as f:
        rows = {r["engine"]: r for r in json.load(f)
                if "hbm_rows_per_step" in r}
    if not rows:
        raise ContractViolation(
            f"no @zipf50k traffic rows found in {baseline_path}")
    reports = []
    for key, hot in ZIPF50K_ROWS.items():
        if key not in rows:
            raise ContractViolation(f"baseline row {key!r} missing from "
                                    f"{baseline_path}")
        predicted = zipf50k_row_traffic(hot_rows=hot)
        baseline = int(rows[key]["hbm_rows_per_step"])
        if predicted != baseline:
            raise ContractViolation(
                f"DMA-traffic contract violated [{key}]: planner predicts "
                f"{predicted} rows/step, committed baseline carries "
                f"{baseline}")
        reports.append(TrafficReport(key, predicted, baseline))
    return reports


def main(argv=None) -> int:
    import argparse

    from repro.core.engine import ENGINE_NAMES, get_engine

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default="BENCH_wallclock.json")
    ap.add_argument("--skip-traffic", action="store_true")
    args = ap.parse_args(argv)
    V = 150
    ok = True
    for name in ENGINE_NAMES:
        # fit the tiered hot prefix inside the certification vocab
        eng = get_engine(name, hot_rows=64) if name == "pallas_fused_tiered" \
            else get_engine(name)
        try:
            certify_engine_contracts(eng, vocab_size=V)
            print(f"contracts: {name:22s} zero-collective ✓  "
                  f"table-aliasing ✓")
        except ContractViolation as e:
            ok = False
            print(f"contracts: {name:22s} FAILED: {e}")
    if not args.skip_traffic:
        try:
            for r in certify_bench_traffic(args.baseline):
                print(f"contracts: {r.engine:22s} planner traffic "
                      f"{r.predicted_rows} rows/step == baseline ✓")
        except (ContractViolation, FileNotFoundError) as e:
            ok = False
            print(f"contracts: traffic FAILED: {e}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
