"""Pipelined HBM-blocked fused SGNS engine: block-planner invariants
(hypothesis property tests on adversarial pair streams), the static
pipeline schedule's ordering guarantees, and interpret-mode
bit-equivalence of ``pallas_fused_pipe`` against the per-block sparse
reference at a shape past the VMEM envelope (``slow`` marker, like the
unpipelined engine's equivalence tests).

The planner/schedule tests run entirely without Pallas — they are pure
functions of the pair stream — so they live in the tier-1 gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sgns
from repro.core.engine import (
    FusedHBMPallasEngine, FusedPipePallasEngine, get_engine)
from repro.core.sgns import SGNSConfig
from repro.data.pairs import build_noise_table
from repro.kernels.sgns_fused import fused_negative_ids
from repro.kernels.sgns_fused_pipe import (
    NUM_SLOTS, kernel_schedule, plan_blocks, resolve_schedule,
    sgns_fused_pipe_step)

# Past the VMEM-resident kernel's envelope, like tests/test_fused_hbm.py:
# 2 tables × 34_000 × 64 × 4 B ≈ 17.4 MB > ~16 MB VMEM.
V_BIG, D_BIG = 34_000, 64
B, K = 64, 4


def _plan(centers, contexts, negs, V, blk, **kw):
    return plan_blocks(jnp.asarray(centers, jnp.int32),
                       jnp.asarray(contexts, jnp.int32),
                       jnp.asarray(negs, jnp.int32), V, blk, **kw)


def _np_plan(plan):
    return jax.tree.map(np.asarray, plan)


# --------------------------------------------------------------- planner
def test_planner_shapes_and_padding():
    rng = np.random.default_rng(0)
    V, blk, Bq, Kq = 50, 8, 19, 3          # 19 = 2 full blocks + tail 3
    p = _np_plan(_plan(rng.integers(0, V, Bq), rng.integers(0, V, Bq),
                       rng.integers(0, V, (Bq, Kq)), V, blk))
    assert p.uw.shape == (3, blk)
    assert p.uc.shape == (3, blk * (Kq + 1))
    assert p.mask.sum() == Bq
    assert (p.mask[-1] == [1, 1, 1] + [0] * 5).all()
    # padded unique slots hold V, real slots hold sorted ids < V
    for b in range(3):
        assert (p.uw[b, p.n_w[b]:] == V).all()
        assert (np.diff(p.uw[b, :p.n_w[b]]) > 0).all()


def test_planner_positions_recover_ids():
    rng = np.random.default_rng(1)
    V, blk, Bq, Kq = 40, 16, 32, 4
    c = rng.integers(0, V, Bq)
    x = rng.integers(0, V, Bq)
    n = rng.integers(0, V, (Bq, Kq))
    p = _np_plan(_plan(c, x, n, V, blk))
    for b in range(2):
        sl = slice(b * blk, (b + 1) * blk)
        np.testing.assert_array_equal(p.uw[b][p.w_pos[b]], c[sl])
        np.testing.assert_array_equal(p.uc[b][p.cp_pos[b]], x[sl])
        np.testing.assert_array_equal(
            p.uc[b][p.cn_pos[b]].reshape(blk, Kq), n[sl])


def test_planner_hazard_flags():
    V, blk = 100, 2
    c = np.array([1, 2, 3, 4, 1, 9], np.int32)   # block 2 reuses row 1...
    x = np.array([11, 12, 13, 14, 15, 16], np.int32)
    n = np.full((6, 1), 77, np.int32)            # every block shares neg 77
    p = _np_plan(_plan(c, x, n, V, blk))
    # C-table: row 77 written by every block ⇒ hazard for blocks 1, 2
    np.testing.assert_array_equal(p.hazard, [0, 1, 1])
    # consecutive blocks disjoint in both tables ⇒ no hazards (block 2
    # reusing block 0's center row 1 is covered by slot recycling)
    n2 = np.arange(6, dtype=np.int32).reshape(6, 1) + 50
    p2 = _np_plan(_plan(c, x, n2, V, blk))
    np.testing.assert_array_equal(p2.hazard, [0, 0, 0])


def test_planner_hazard_is_lookbehind_one_only():
    """Sharing a row with block b-2 (but not b-1) must NOT set the flag:
    the 2-slot ring's recycling wait already serializes against b-2."""
    V, blk = 100, 2
    c = np.array([1, 2, 30, 40, 1, 9], np.int32)  # blocks 0 and 2 share row 1
    x = np.array([11, 12, 13, 14, 15, 16], np.int32)
    n = np.arange(6, dtype=np.int32).reshape(6, 1) + 50
    p = _np_plan(_plan(c, x, n, V, blk))
    np.testing.assert_array_equal(p.hazard, [0, 0, 0])


def test_planner_hazard_window_grows_with_ring_depth():
    """A deeper ring leaves block b-2's write-backs in flight when block
    b gathers, so at ring_depth=3 the same b-2 overlap that slot
    recycling covered at depth 2 becomes a hazard — the look-behind
    window is exactly ring_depth - 1 blocks."""
    V, blk = 100, 2
    c = np.array([1, 2, 30, 40, 1, 9], np.int32)  # blocks 0 and 2 share row 1
    x = np.array([11, 12, 13, 14, 15, 16], np.int32)
    n = np.arange(6, dtype=np.int32).reshape(6, 1) + 50
    p3 = _np_plan(_plan(c, x, n, V, blk, ring_depth=3))
    np.testing.assert_array_equal(p3.hazard, [0, 0, 1])
    # at depth 3 a b-3 overlap is still recycled away, not flagged
    c4 = np.array([1, 2, 30, 40, 50, 60, 1, 9], np.int32)
    x4 = np.array([11, 12, 13, 14, 15, 16, 17, 18], np.int32)
    n4 = np.arange(8, dtype=np.int32).reshape(8, 1) + 50
    p4 = _np_plan(_plan(c4, x4, n4, V, blk, ring_depth=3))
    np.testing.assert_array_equal(p4.hazard, [0, 0, 0, 0])


# -------------------------------------------------------------- schedule
def _check_schedule(events, nblocks, row_sets, hazard, num_slots=NUM_SLOTS):
    """The three pipeline-safety properties on a concrete event order."""
    S = num_slots
    pos = {}
    for i, ev in enumerate(events):
        pos[ev] = i
    for b in range(nblocks):
        s = b % S
        # basic dataflow per block
        assert pos[("gather", b, s)] < pos[("wait_gather", b, s)]
        assert pos[("wait_gather", b, s)] < pos[("compute", b, s)]
        assert pos[("compute", b, s)] < pos[("scatter", b, s)]
        assert pos[("scatter", b, s)] < pos[("wait_scatter", b, s)]
        # no slot reuse before its semaphore wait: block b's gathers
        # overwrite block b-S's buffers, whose scatters read from them
        if b >= S:
            prev = (b - S, (b - S) % S)
            assert pos[("wait_scatter", *prev)] < pos[("gather", b, s)], \
                f"slot of block {b} reused before block {b - S}'s " \
                f"scatters drained"
        # scatter-before-regather: any earlier block writing a row this
        # block touches must have fully drained before this gather
        for b0 in range(b):
            if row_sets[b0] & row_sets[b]:
                assert pos[("wait_scatter", b0, b0 % S)] < \
                    pos[("gather", b, s)], \
                    f"block {b} gathers rows block {b0} still scatters"
    # every op happens exactly once per block
    assert len(events) == len(pos)
    from collections import Counter
    counts = Counter(op for op, _, _ in events)
    assert counts == {op: nblocks for op in
                      ("gather", "wait_gather", "compute", "scatter",
                       "wait_scatter")}


def test_schedule_static_structure():
    """For each per-block event, the guards over its occurrence sites
    PARTITION the hazard-outcome space: under every hazard vector the
    event resolves exactly once, so every DMA is started and waited
    exactly once no matter how the flags come out."""
    import itertools

    for S in (2, 3, 4):
        for nblocks in (1, 2, 3, 5):
            sites = {}
            for op, b, s, g in kernel_schedule(nblocks, S):
                sites.setdefault((op, b, s), []).append(g)
            for bits in itertools.product((False, True), repeat=nblocks):
                for key, guards in sites.items():
                    hits = sum(
                        1 for g in guards
                        if g is None or all(bits[f] is w for f, w in g))
                    assert hits == 1, (S, nblocks, key, bits, guards)


def test_schedule_rejects_degenerate_ring():
    with pytest.raises(ValueError, match="2 slots"):
        kernel_schedule(4, 1)


def test_schedule_resolves_safely_for_all_hazard_vectors():
    """Exhaustive over hazard outcomes at small nblocks and ring depths:
    every resolved event order keeps the dataflow/slot/once-each
    properties (hazard row-set interactions are exercised by the
    hypothesis test below)."""
    import itertools

    for S in (2, 3):
        for nblocks in (1, 2, 4, 5):
            for bits in itertools.product((0, 1), repeat=nblocks - 1):
                hz = (0,) + bits
                ev = resolve_schedule(hz, S)
                # row sets consistent with the hazard vector: hazard[b]=1
                # means block b shares block b-1's own row, else block b
                # is disjoint from every block in its look-behind window
                row_sets = [{(b, 0)} for b in range(nblocks)]
                for b in range(1, nblocks):
                    if hz[b]:
                        row_sets[b].add((b - 1, 0))
                _check_schedule(ev, nblocks, row_sets, hz, S)


# ----------------------------------------- invariants on adversarial streams
def _assert_planner_invariants(c, x, n, V, blk, ring_depth=NUM_SLOTS):
    """The pipeline-safety contract for one pair stream: dedup (every
    touched row gathered exactly once per block), exact windowed
    look-behind hazard flags (ring_depth - 1 blocks), and a resolved
    schedule whose event order respects slot recycling and
    scatter-before-regather for the stream's actual row sets."""
    p = _np_plan(_plan(c, x, n, V, blk, ring_depth=ring_depth))
    blk_eff = p.w_pos.shape[1]
    nblocks = p.uw.shape[0]

    w_sets, c_sets = [], []
    for b in range(nblocks):
        valid = p.mask[b].astype(bool)
        nv = int(valid.sum())
        cen = c[b * blk_eff:b * blk_eff + nv]
        ctx = x[b * blk_eff:b * blk_eff + nv]
        neg = n[b * blk_eff:b * blk_eff + nv]
        touched_w = set(cen.tolist())
        touched_c = set(ctx.tolist()) | set(neg.reshape(-1).tolist())
        # every touched row gathered exactly once per block (gather list
        # = the valid unique slots: strictly sorted ⇒ no duplicates)
        gw = p.uw[b, :p.n_w[b]]
        gc = p.uc[b, :p.n_c[b]]
        assert (np.diff(gw) > 0).all() and (np.diff(gc) > 0).all()
        # padded pairs only ever reference already-touched rows, so the
        # gather sets must cover and not exceed touched ∪ pad-source
        if valid.all():
            assert set(gw.tolist()) == touched_w
            assert set(gc.tolist()) == touched_c
        else:
            assert touched_w <= set(gw.tolist()) <= touched_w | {int(c[0])}
            assert touched_c <= set(gc.tolist()) <= (
                touched_c | {int(x[0])} | set(n[0].tolist()))
        w_sets.append(set(gw.tolist()))
        c_sets.append(set(gc.tolist()))

    # hazard flags are exactly the windowed look-behind intersections
    for b in range(nblocks):
        expect = any((w_sets[b] & w_sets[b - m]) or (c_sets[b] & c_sets[b - m])
                     for m in range(1, min(ring_depth, b + 1)))
        assert bool(p.hazard[b]) == expect, (b, p.hazard)

    # the resolved schedule keeps slot/hazard/dataflow safety for the
    # actual row sets of this stream (W and C live in separate buffers,
    # so the combined per-block "row set" tags rows by table)
    row_sets = [{("w", r) for r in w_sets[b]} | {("c", r) for r in c_sets[b]}
                for b in range(nblocks)]
    _check_schedule(resolve_schedule(p.hazard, ring_depth), nblocks,
                    row_sets, p.hazard, ring_depth)


def test_planner_invariants_on_seeded_adversarial_streams():
    """Deterministic sweep of the same invariants hypothesis fuzzes:
    tiny vocabularies (maximal row collisions), single-pair blocks,
    non-dividing batches, K=1..4."""
    rng = np.random.default_rng(42)
    cases = [(5, 7, 1, 1, 2), (5, 17, 2, 3, 2), (7, 40, 3, 16, 2),
             (60, 33, 4, 8, 3), (11, 24, 2, 5, 3), (31, 1, 1, 4, 4),
             (5, 17, 2, 3, 3)]
    for V, Bq, Kq, blk, rd in cases:
        for _ in range(8):
            _assert_planner_invariants(
                rng.integers(0, V, Bq).astype(np.int32),
                rng.integers(0, V, Bq).astype(np.int32),
                rng.integers(0, V, (Bq, Kq)).astype(np.int32), V, blk,
                ring_depth=rd)


def _numpy_plan(c, x, n, V, blk, hot_rows, ring_depth):
    """The planner's outputs built the plain way, block by block:
    ``np.unique`` for the cold row sets, ``np.searchsorted`` for the
    buffer positions, set intersections for the hazard flags."""
    Bq, Kq = n.shape
    nblocks = -(-Bq // blk)
    pad = nblocks * blk - Bq
    c, x, n = (np.concatenate([a, np.repeat(a[:1], pad, axis=0)])
               for a in (c, x, n))
    cold = lambda ids: np.where(ids < hot_rows, V, ids)
    out = {f: [] for f in ("uw", "uc", "n_w", "n_c", "w_pos", "cp_pos",
                           "cn_pos")}
    for b in range(nblocks):
        sl = slice(b * blk, (b + 1) * blk)
        w_ids = cold(c[sl])
        c_ids = cold(np.concatenate([x[sl], n[sl].reshape(-1)]))
        for name, ids in (("w", w_ids), ("c", c_ids)):
            u = np.unique(ids[ids < V])
            out[f"n_{name}"].append(len(u))
            u = np.concatenate([u, np.full(len(ids) - len(u), V)])
            out[f"u{name}"].append(u)
            pos = np.minimum(np.searchsorted(u, ids, side="left"),
                             len(ids) - 1)
            if name == "w":
                out["w_pos"].append(pos)
            else:
                out["cp_pos"].append(pos[:blk])
                out["cn_pos"].append(pos[blk:])
    sets = [(set(out["uw"][b][:out["n_w"][b]]),
             set(out["uc"][b][:out["n_c"][b]])) for b in range(nblocks)]
    hazard = [any((sets[b][0] & sets[b - m][0]) or (sets[b][1] & sets[b - m][1])
                  for m in range(1, min(ring_depth, b + 1)))
              for b in range(nblocks)]
    out = {f: np.asarray(v) for f, v in out.items()}
    out["hazard"] = np.asarray(hazard, np.int32)
    return out


@pytest.mark.parametrize("ring_depth", [2, 3, 4])
@pytest.mark.parametrize("hot", ["none", "one", "blk"])
def test_planner_matches_numpy_reference(hot, ring_depth):
    """Every index map of the plan equals the plain NumPy construction,
    element for element, on duplicate-heavy seeded streams whose last
    block is padded, with no hot tier, a one-row hot tier and a hot tier
    as wide as a block. Zipf draws repeat rows within blocks and across
    neighbours (hazards everywhere); block-local draws repeat rows only
    within a block (padded unique sets, no hazard but the tail's)."""
    rng = np.random.default_rng(1000 + 10 * ring_depth + len(hot))
    zipf = lambda V, s: np.minimum(rng.zipf(1.3, s) - 1, V - 1)
    local = lambda V, s: (np.arange(s[0]).reshape((-1,) + (1,) * (len(s) - 1))
                          // 8 * 8 + rng.integers(0, 5, s)) % V
    for draw, V, Bq, Kq, blk in [(zipf, 40, 45, 3, 8), (zipf, 300, 100, 5, 16),
                                 (zipf, 12, 23, 2, 4), (zipf, 2000, 70, 4, 32),
                                 (local, 400, 45, 3, 8)]:
        hot_rows = {"none": 0, "one": 1, "blk": blk}[hot]
        for _ in range(3):
            c, x, n = (draw(V, s).astype(np.int32)
                       for s in ((Bq,), (Bq,), (Bq, Kq)))
            ref = _numpy_plan(c, x, n, V, blk, hot_rows, ring_depth)
            p = _np_plan(_plan(c, x, n, V, blk, hot_rows=hot_rows,
                               ring_depth=ring_depth))
            assert p.mask[-1].sum() < blk          # a padded tail block
            for f, want in ref.items():
                got = getattr(p, f)
                assert got.dtype == np.int32, f
                np.testing.assert_array_equal(got, want, err_msg=f)


try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                     # pragma: no cover
    HAS_HYPOTHESIS = False

if HAS_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(data=st.data(), V=st.integers(5, 60), Bq=st.integers(1, 40),
           Kq=st.integers(1, 4), blk=st.integers(1, 16),
           rd=st.integers(2, 4))
    def test_planner_invariants_on_adversarial_streams(data, V, Bq, Kq, blk,
                                                       rd):
        ids = st.integers(0, V - 1)
        c = np.array(data.draw(st.lists(ids, min_size=Bq, max_size=Bq)),
                     np.int32)
        x = np.array(data.draw(st.lists(ids, min_size=Bq, max_size=Bq)),
                     np.int32)
        n = np.array(data.draw(st.lists(
            st.lists(ids, min_size=Kq, max_size=Kq),
            min_size=Bq, max_size=Bq)), np.int32)
        _assert_planner_invariants(c, x, n, V, blk, ring_depth=rd)


# ------------------------------------------------------------- equivalence
@pytest.fixture(scope="module")
def cfg():
    return SGNSConfig(vocab_size=V_BIG, dim=D_BIG, negatives=K)


@pytest.fixture(scope="module")
def world(cfg):
    rng = np.random.default_rng(0)
    params = {
        "W": jnp.asarray(0.01 * rng.normal(size=(V_BIG, D_BIG)), jnp.float32),
        "C": jnp.asarray(0.01 * rng.normal(size=(V_BIG, D_BIG)), jnp.float32),
    }
    c = jnp.asarray(rng.integers(0, V_BIG, B, dtype=np.int32))
    x = jnp.asarray(rng.integers(0, V_BIG, B, dtype=np.int32))
    # duplicates within a block: dedup + in-VMEM accumulation must match
    # the reference's duplicate-accumulating scatter-add bit for bit
    c = c.at[1].set(c[0])
    x = x.at[3].set(x[2])
    counts = rng.zipf(1.3, V_BIG).astype(np.float64)
    table = build_noise_table(counts, kind="alias")
    return params, c, x, table


def _sparse_blocked(params, c, x, ids, lr, blk):
    step = jax.jit(sgns.train_step_sparse)
    params = jax.tree.map(jnp.copy, params)
    for b0 in range(0, c.shape[0], blk):
        params, _ = step(params, c[b0:b0 + blk], x[b0:b0 + blk],
                         ids[b0:b0 + blk], lr)
    return params


@pytest.mark.slow
@pytest.mark.parametrize("blk,ring", [(16, 2), (40, 2), (16, 3)])
def test_pipe_bit_identical_to_per_block_sparse(cfg, world, blk, ring):
    """Past the VMEM envelope: the pipelined step ≡ the per-block sparse
    reference on the replayed negatives, bit for bit — including when
    the batch pads to a partial final block and at a deepened ring."""
    params, c, x, table = world
    key = jax.random.PRNGKey(11)
    lr = jnp.float32(0.025)
    ph, _ = sgns_fused_pipe_step(
        jax.tree.map(jnp.copy, params), c, x, table, key, lr,
        negatives=K, block_pairs=blk, ring_depth=ring, interpret=True)
    ids = fused_negative_ids(key.astype(jnp.uint32), table["prob"],
                             table["alias"], (B, K))
    pr = _sparse_blocked(params, c, x, ids, lr, blk)
    np.testing.assert_array_equal(np.asarray(ph["W"]), np.asarray(pr["W"]))
    np.testing.assert_array_equal(np.asarray(ph["C"]), np.asarray(pr["C"]))


@pytest.mark.slow
def test_pipe_bit_identical_to_unpipelined_hbm_engine(cfg, world):
    """pallas_fused_pipe ≡ pallas_fused_hbm at the engine level: the DMA
    pipeline must not move a single bit relative to the serial chain."""
    params, c, x, table = world
    key = jax.random.PRNGKey(5)
    kw = dict(block_pairs=16, interpret=True)
    sp = get_engine("pallas_fused_pipe", **kw).make_step(cfg, 1000)
    sh = get_engine("pallas_fused_hbm", **kw).make_step(cfg, 1000)
    pp, lp = sp(jax.tree.map(jnp.copy, params), c, x, table, key, jnp.int32(2))
    ph, lh = sh(jax.tree.map(jnp.copy, params), c, x, table, key, jnp.int32(2))
    np.testing.assert_array_equal(np.asarray(pp["W"]), np.asarray(ph["W"]))
    np.testing.assert_array_equal(np.asarray(pp["C"]), np.asarray(ph["C"]))
    assert float(lp) == pytest.approx(float(lh), rel=1e-6)


@pytest.mark.slow
def test_pipe_sequential_falls_back_to_per_pair_oracle(cfg, world):
    """sequential=True on the pipe engine runs the unpipelined per-pair
    kernel — bit-identical to the hbm engine's sequential path."""
    params, c, x, table = world
    B2 = 16
    key = jax.random.PRNGKey(23)
    pe = get_engine("pallas_fused_pipe", block_pairs=8, sequential=True,
                    interpret=True)
    he = get_engine("pallas_fused_hbm", block_pairs=8, sequential=True,
                    interpret=True)
    pp, _ = pe.make_step(cfg, 1000)(jax.tree.map(jnp.copy, params),
                                    c[:B2], x[:B2], table, key, jnp.int32(0))
    ph, _ = he.make_step(cfg, 1000)(jax.tree.map(jnp.copy, params),
                                    c[:B2], x[:B2], table, key, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(pp["W"]), np.asarray(ph["W"]))
    np.testing.assert_array_equal(np.asarray(pp["C"]), np.asarray(ph["C"]))


# ------------------------------------------------------------ engine wiring
def test_engine_fields_and_registry():
    eng = get_engine("pallas_fused_pipe")
    assert isinstance(eng, FusedPipePallasEngine)
    assert isinstance(eng, FusedHBMPallasEngine)    # inherits hbm fields
    assert eng.table_kind == "alias"
    assert eng.block_pairs == 256 and eng.sequential is False
    assert eng.ring_depth == 2
    assert get_engine("pallas_fused_pipe", block_pairs=64).block_pairs == 64
    assert get_engine("pallas_fused_pipe", ring_depth=3).ring_depth == 3
    with pytest.raises(ValueError, match="alias"):
        get_engine("pallas_fused_pipe:cdf")
    with pytest.raises(ValueError, match="ring_depth"):
        get_engine("pallas_fused_pipe", ring_depth=1)


def test_trainer_epoch_trains_with_pipe_engine():
    """AsyncShardTrainer (vmap backend, scan over steps) runs the
    pipelined engine end to end and the loss drops below the init
    plateau — the wiring the driver and CLIs sit on."""
    from repro.core.async_trainer import AsyncShardTrainer

    cfg = SGNSConfig(vocab_size=150, dim=32, negatives=4)
    rng = np.random.default_rng(0)
    n, S, Bt = 2, 12, 64
    c = jnp.asarray(rng.integers(0, 30, (n, S, Bt)), jnp.int32)
    x = jnp.asarray((np.asarray(c) + 1) % 30, jnp.int32)
    counts = rng.zipf(1.3, cfg.vocab_size).astype(np.float64)
    table = jax.tree.map(lambda a: jnp.stack([a, a]),
                         build_noise_table(counts, kind="alias"))
    tr = AsyncShardTrainer(cfg=cfg, num_workers=n, total_steps=S,
                           engine=get_engine("pallas_fused_pipe",
                                             block_pairs=16))
    p = tr.init(jax.random.PRNGKey(0))
    p, losses = tr.epoch(p, c, x, table, jax.random.PRNGKey(4))
    assert np.isfinite(np.asarray(losses)).all()
    assert float(losses[:, -1].mean()) < (cfg.negatives + 1) * np.log(2)
