"""Static-analysis layer: the shipping artifacts certify clean, and —
the part that makes the checkers trustworthy — every seeded mutation
(dropped DMA wait, slot collision, off-by-one hazard window, planted
collective, broken donation aliasing, over-budget config, lint-rule
violations, tampered bench baseline) is caught."""

import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import dma_model
from repro.analysis.contracts import (
    ContractViolation, certify_bench_traffic, certify_table_aliasing,
    certify_zero_collective, count_collective_ops, parse_op_counts)
from repro.analysis.lint_rules import run_lint
from repro.analysis.vmem import (
    VmemBudgetError, check_vmem_budget, estimate_vmem)
from repro.kernels.sgns_fused_pipe import kernel_schedule, plan_blocks


# ---------------------------------------------------------------- dma model
def test_shipping_schedule_certifies():
    rep = dma_model.check_schedule_space(ring_depths=(2, 3, 4),
                                         max_nblocks=4)
    assert rep.ok, rep.summary()
    assert rep.schedules_checked > 0


def test_shipping_planner_certifies():
    rep = dma_model.check_planner(ring_depths=(2, 3), max_nblocks=3)
    assert rep.ok, rep.summary()
    assert rep.plans_checked > 0


def test_dropped_dma_wait_is_caught():
    """Mutation: a schedule that never waits on the last block's
    write-back ships an unretired DMA — every resolution must flag it."""
    def mutant(nblocks, S):
        return [e for e in kernel_schedule(nblocks, S)
                if not (e[0] == "wait_scatter" and e[1] == nblocks - 1)]

    rep = dma_model.check_schedule_space(ring_depths=(2,), max_nblocks=3,
                                         schedule_fn=mutant)
    assert not rep.ok
    assert all(v.rule == "matched-dma" for v in rep.violations)


def test_slot_collision_is_caught():
    """Hand-built sequence: block 2's gather reuses slot 0 before block
    0's write-back even started — the ring-slot race."""
    events = [
        ("gather", 0, 0), ("wait_gather", 0, 0), ("compute", 0, 0),
        ("gather", 1, 1), ("wait_gather", 1, 1), ("compute", 1, 1),
        ("gather", 2, 0),                       # <-- rewrites live buf[0]
        ("scatter", 0, 0), ("wait_scatter", 0, 0),
        ("scatter", 1, 1), ("wait_scatter", 1, 1),
        ("wait_gather", 2, 0), ("compute", 2, 0),
        ("scatter", 2, 0), ("wait_scatter", 2, 0),
    ]
    out = dma_model.check_events(
        events, nblocks=3, ring_depth=2,
        may_overlap=lambda b0, b: False)
    assert any(v.rule == "slot-race" for v in out), [str(v) for v in out]


def test_off_by_one_hazard_window_is_caught():
    """S=3, hazard flags block 2 against its window {0, 1}; draining
    only block 1 before gather 2 (the off-by-one) leaves block 0's
    write-back racing the regather."""
    hazard = (0, 0, 1)
    events = [
        ("gather", 0, 0), ("wait_gather", 0, 0), ("compute", 0, 0),
        ("gather", 1, 1), ("wait_gather", 1, 1), ("compute", 1, 1),
        ("scatter", 0, 0), ("scatter", 1, 1),
        ("wait_scatter", 1, 1),                 # <-- block 0 left in flight
        ("gather", 2, 2), ("wait_gather", 2, 2), ("compute", 2, 2),
        ("wait_scatter", 0, 0),
        ("scatter", 2, 2), ("wait_scatter", 2, 2),
    ]
    out = dma_model.check_events(
        events, nblocks=3, ring_depth=3, hazard=hazard,
        may_overlap=dma_model.hazard_may_overlap(hazard, 3))
    assert any(v.rule == "war-hazard" and "block 0" in v.detail
               for v in out), [str(v) for v in out]
    # the correctly drained order certifies clean
    fixed = events[:8] + [("wait_scatter", 0, 0), ("wait_scatter", 1, 1)] \
        + [e for e in events[8:] if e != ("wait_scatter", 1, 1)
           and e != ("wait_scatter", 0, 0)]
    assert dma_model.check_events(
        fixed, nblocks=3, ring_depth=3, hazard=hazard,
        may_overlap=dma_model.hazard_may_overlap(hazard, 3)) == []


def test_planner_that_drops_hazards_is_caught():
    """Mutation: a planner that reports no hazards diverges from the
    independent windowed look-behind oracle."""
    def mutant(c, x, n, V, blk, *, hot_rows=0, ring_depth=2):
        plan = plan_blocks(c, x, n, V, blk, hot_rows=hot_rows,
                           ring_depth=ring_depth)
        return plan._replace(hazard=jnp.zeros_like(plan.hazard))

    rep = dma_model.check_planner(ring_depths=(2,), max_nblocks=2,
                                  plan_fn=mutant)
    assert not rep.ok
    assert any(v.rule == "war-hazard" for v in rep.violations)


# ---------------------------------------------------------------- contracts
def _psum_lowered():
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("w",))
    f = jax.shard_map(lambda v: jax.lax.psum(v, "w"), mesh=mesh,
                      in_specs=P("w"), out_specs=P(), check_vma=False)
    return jax.jit(f).lower(jax.ShapeDtypeStruct((4,), jnp.float32))


def test_planted_psum_is_caught_on_lowered_mlir():
    """The regression the certifier exists for: lowered text is
    StableHLO MLIR (underscore spellings) where the old hyphen-matching
    HLO regex found nothing — the structured op-walk must catch the
    planted psum in both the lowered and the compiled form."""
    lowered = _psum_lowered()
    txt = lowered.as_text()
    assert "all_reduce" in txt                      # it IS the MLIR form
    hits = count_collective_ops(txt)
    assert hits and all("all_reduce" in k for k in hits), hits
    with pytest.raises(ContractViolation, match="zero-collective"):
        certify_zero_collective(lowered, label="planted")
    compiled_txt = lowered.compile().as_text()
    assert count_collective_ops(compiled_txt), "compiled HLO form missed"


def test_collective_name_in_strings_is_not_a_false_positive():
    """Metadata/location strings mentioning collective names are not
    ops; only op-position identifiers count."""
    fp_text = '\n'.join([
        '  %0 = stablehlo.add %arg0, %arg1 : tensor<4xf32> '
        'loc("all_reduce_helper/add")',
        '  %1 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b), '
        'metadata={op_name="jit(all-reduce-wrapper)/add"}',
        '  // the all-reduce that is not there',
    ])
    assert count_collective_ops(fp_text) == {}
    counts = parse_op_counts(fp_text)
    assert counts.get("stablehlo.add") == 1 and counts.get("add") == 1


def test_merge_gram_is_the_one_intentional_collective():
    """The zero-collective contract is scoped to the TRAIN path. The
    merge phase's sharded Gram reduction is the one intentional
    collective: its lowering must show exactly one all_gather — visible
    to the same certifier that keeps the train path clean — and the
    certifier must (correctly) reject it if pointed there."""
    import jax

    from repro.sharding.merge import lower_mesh_gram

    mesh = jax.make_mesh((1,), ("worker",))
    lowered = lower_mesh_gram(64, 8, mesh, num_shards=4)
    hits = count_collective_ops(lowered.as_text())
    assert hits == {"stablehlo.all_gather": 1}, hits
    with pytest.raises(ContractViolation, match="zero-collective"):
        certify_zero_collective(lowered, label="merge-gram")


def test_broken_table_donation_aliasing_is_caught():
    """Mutation: a step whose outputs cannot reuse the donated (V, d)
    buffers (transposed tables) must fail the aliasing certificate."""
    from repro.core.engine import SparseEngine

    class TransposingEngine(SparseEngine):
        def make_step(self, cfg, total_steps):
            inner = super().make_step(cfg, total_steps)

            def step(params, c, x, table, key, i):
                params, loss = inner(params, c, x, table, key, i)
                return jax.tree.map(jnp.transpose, params), loss

            return step

    with pytest.raises(ContractViolation, match="aliasing"):
        certify_table_aliasing(TransposingEngine(), vocab_size=96, dim=16,
                               negatives=2, batch=32)
    # the unmutated engine certifies
    rep = certify_table_aliasing("sparse", vocab_size=96, dim=16,
                                 negatives=2, batch=32)
    assert rep.aliased_table_args >= 2


def test_bench_traffic_certificate_and_tamper_detection(tmp_path):
    """The committed @zipf50k baseline matches the planner; a tampered
    row is caught."""
    reports = certify_bench_traffic("BENCH_wallclock.json")
    assert {r.engine for r in reports} == {
        "pallas_fused_tiered[hot_rows=0]@zipf50k",
        "pallas_fused_tiered@zipf50k"}
    rows = [r for r in json.load(open("BENCH_wallclock.json"))]
    for r in rows:
        if r.get("engine") == "pallas_fused_tiered@zipf50k":
            r["hbm_rows_per_step"] += 2          # silent planner drift
    tampered = tmp_path / "BENCH_wallclock.json"
    tampered.write_text(json.dumps(rows))
    with pytest.raises(ContractViolation, match="traffic"):
        certify_bench_traffic(str(tampered))


# --------------------------------------------------------------------- vmem
def test_vmem_estimates_scale_with_dials():
    shape = dict(vocab_size=50_000, dim=128, negatives=5, batch=1024)
    for eng in ("dense", "sparse"):
        assert estimate_vmem(eng, **shape).total_bytes == 0
    from repro.core.engine import get_engine

    ring2 = estimate_vmem(get_engine("pallas_fused_tiered"), **shape)
    ring4 = estimate_vmem(get_engine("pallas_fused_tiered", ring_depth=4),
                          **shape)
    assert ring4.total_bytes > ring2.total_bytes
    t0 = estimate_vmem(get_engine("pallas_fused_tiered", hot_rows=0),
                       **shape)
    t1 = estimate_vmem(get_engine("pallas_fused_tiered", hot_rows=4096),
                       **shape)
    assert t1.total_bytes > t0.total_bytes
    assert t1.terms["hot_prefix"] > t0.terms["hot_prefix"]


def test_vmem_budget_rejects_resident_tables_at_paper_shape():
    from repro.core.engine import get_engine

    paper = dict(vocab_size=300_000, dim=500, negatives=5, batch=1024)
    # a hot prefix of all V rows holds both tables in VMEM
    with pytest.raises(VmemBudgetError, match="hot_rows"):
        check_vmem_budget(get_engine("pallas_fused_tiered",
                                     hot_rows=paper["vocab_size"]), **paper)
    # HBM-resident tables exist exactly to fit this shape
    est = check_vmem_budget(get_engine("pallas_fused_tiered", hot_rows=256),
                            **paper)
    assert est.total_bytes <= 16 * 2 ** 20


def test_vmem_budget_rejects_oversized_dials():
    with pytest.raises(VmemBudgetError, match="hot_rows"):
        from repro.core.engine import get_engine
        check_vmem_budget(
            get_engine("pallas_fused_tiered", hot_rows=200_000),
            vocab_size=300_000, dim=500, negatives=5, batch=1024)


# --------------------------------------------------------------------- lint
def _write(tmp_path, rel, text):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return p


def test_lint_flags_each_rule(tmp_path):
    _write(tmp_path, "core/seeds.py",
           "import jax\n"
           "def f(seed, worker):\n"
           "    return jax.random.PRNGKey(seed + worker)\n")
    _write(tmp_path, "data/draw.py",
           "import numpy as np\n"
           "def g(cdf, u):\n"
           "    return np.searchsorted(cdf, u)\n"
           "def h(cdf, u):\n"
           "    return np.searchsorted(cdf, u, side='left')\n")
    _write(tmp_path, "kernels/rng.py",
           "import numpy as np\n"
           "import random\n"
           "from numpy.random import default_rng\n"
           "def f():\n"
           "    np.random.seed(0)\n"
           "    rng = default_rng()\n"
           "    return random.random()\n")
    _write(tmp_path, "kernels/coll.py",
           "from jax import lax\n"
           "def f(x):\n"
           "    return lax.psum(x, 'w')\n")
    rules = {f.rule for f in run_lint(tmp_path)}
    assert rules == {"RL001", "RL002", "RL003", "RL004"}
    by_rule = {}
    for f in run_lint(tmp_path):
        by_rule.setdefault(f.rule, []).append(f)
    assert len(by_rule["RL002"]) == 2     # missing side + wrong side
    assert len(by_rule["RL003"]) == 3     # legacy, stdlib, unseeded


def test_lint_pragma_suppresses_and_scoping_limits(tmp_path):
    _write(tmp_path, "core/ok.py",
           "import numpy as np\n"
           "np.random.seed(0)  # repro-lint: ignore[RL003]\n")
    # same hazards OUTSIDE core//kernels/ are out of scope for RL003/4
    _write(tmp_path, "benchmarks_like/timing.py",
           "import numpy as np\n"
           "np.random.seed(0)\n"
           "from jax import lax\n"
           "def f(x):\n"
           "    return lax.psum(x, 'w')\n")
    assert run_lint(tmp_path) == []
    # async_trainer hosts the sync baselines: RL004 does not apply there
    _write(tmp_path, "core/async_trainer.py",
           "from jax import lax\n"
           "def f(x):\n"
           "    return lax.psum(x, 'w')\n")
    assert run_lint(tmp_path) == []


def test_lint_real_tree_is_clean():
    assert [str(f) for f in run_lint("src/repro")] == []


# ----------------------------------------------------------------- wiring
def test_trainer_collective_helpers_delegate_to_contracts():
    """core.assert_no_collectives must catch the MLIR spelling now (the
    old regex did not) — the dedupe is behavioral, not cosmetic."""
    from repro.core import assert_no_collectives
    from repro.core import count_collective_ops as core_counts

    lowered = _psum_lowered()
    with pytest.raises(AssertionError):
        assert_no_collectives(lowered)
    assert core_counts(lowered.as_text())
