"""The fused SGNS engine ``pallas_fused_tiered``: tier-routing planner
invariants (every touched row served by exactly one tier, hot rows never
in the DMA lists, cold-side dedup/hazard contract intact — unit +
hypothesis property tests), engine wiring, and interpret-mode
bit-equivalence of the kernel against the per-block sparse reference at
a shape past the VMEM envelope, over hot fractions {0, small, all},
block sizes and ring depths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sgns
from repro.core.engine import (
    FusedTieredPallasEngine, UpdateEngine, get_engine)
from repro.core.sgns import SGNSConfig
from repro.data.pairs import build_noise_table
from repro.kernels.alias_draw import _as_seed, fused_negative_ids
from repro.kernels.sgns_fused_pipe import (
    fused_pipe_step, plan_blocks, resolve_schedule)

# Past the VMEM envelope: 2 tables × 34_000 × 64 × 4 B ≈ 17.4 MB > ~16 MB.
V_BIG, D_BIG = 34_000, 64
B, K = 64, 4


def _plan(centers, contexts, negs, V, blk, **kw):
    return jax.tree.map(np.asarray, plan_blocks(
        jnp.asarray(centers, jnp.int32), jnp.asarray(contexts, jnp.int32),
        jnp.asarray(negs, jnp.int32), V, blk, **kw))


# ------------------------------------------------------- tier routing
def _assert_tier_routing_invariants(c, x, n, V, blk, hot, ring_depth=2):
    """The tiered-planner contract for one pair stream:

    * every touched row is served by exactly one tier — cold rows appear
      exactly once in their block's gather list, hot rows never do;
    * the cold-side dedup / position-map / windowed-hazard invariants of
      the pure pipeline hold over the cold rows alone;
    * the blocked id arrays (``cen``/``ctx``/``neg``) recover the input
      stream, so the kernel's direct hot indices are the true ids.
    """
    p = _plan(c, x, n, V, blk, hot_rows=hot, ring_depth=ring_depth)
    blk_eff = p.w_pos.shape[1]
    nblocks = p.uw.shape[0]
    Kq = n.shape[1]

    # blocked ids recover the (padded) stream
    flat_c = p.cen.reshape(-1)[:len(c)]
    flat_x = p.ctx.reshape(-1)[:len(x)]
    flat_n = p.neg.reshape(nblocks, blk_eff, Kq).reshape(-1, Kq)[:len(n)]
    np.testing.assert_array_equal(flat_c, c)
    np.testing.assert_array_equal(flat_x, x)
    np.testing.assert_array_equal(flat_n, n)

    w_sets, c_sets = [], []
    for b in range(nblocks):
        valid = p.mask[b].astype(bool)
        nv = int(valid.sum())
        cen = c[b * blk_eff:b * blk_eff + nv]
        ctx = x[b * blk_eff:b * blk_eff + nv]
        neg = n[b * blk_eff:b * blk_eff + nv]
        touched_w = set(cen.tolist())
        touched_c = set(ctx.tolist()) | set(neg.reshape(-1).tolist())
        cold_w = {r for r in touched_w if r >= hot}
        cold_c = {r for r in touched_c if r >= hot}
        gw = p.uw[b, :p.n_w[b]]
        gc = p.uc[b, :p.n_c[b]]
        # dedup: strictly sorted ⇒ each cold row exactly once
        assert (np.diff(gw) > 0).all() and (np.diff(gc) > 0).all()
        # hot rows NEVER enter the gather/scatter lists
        assert (gw >= hot).all() and (gc >= hot).all()
        # padding slots hold the V sentinel
        assert (p.uw[b, p.n_w[b]:] == V).all()
        assert (p.uc[b, p.n_c[b]:] == V).all()
        # exactly-once coverage: the cold gather set covers the block's
        # cold touched rows (plus at most the pad-source pair's cold
        # rows when the tail block is padded); hot rows are covered by
        # the id arrays checked above — (hot ∪ cold) is a partition of
        # touched because tier membership is a pure id predicate
        if valid.all():
            assert set(gw.tolist()) == cold_w
            assert set(gc.tolist()) == cold_c
        else:
            pad_w = {int(c[0])} if int(c[0]) >= hot else set()
            pad_c = {r for r in ({int(x[0])} | set(n[0].tolist()))
                     if r >= hot}
            assert cold_w <= set(gw.tolist()) <= cold_w | pad_w
            assert cold_c <= set(gc.tolist()) <= cold_c | pad_c
        # position maps: every pair element resolves either hot (id <
        # hot, not positioned in the buffer's valid region) or to the
        # buffer slot holding exactly its row
        pc = p.cen[b]
        for j in range(blk_eff):
            if pc[j] >= hot:
                assert p.uw[b][p.w_pos[b][j]] == pc[j]
            else:
                assert p.w_pos[b][j] >= p.n_w[b]   # masked pad slot
        px = p.ctx[b]
        for j in range(blk_eff):
            if px[j] >= hot:
                assert p.uc[b][p.cp_pos[b][j]] == px[j]
            else:
                assert p.cp_pos[b][j] >= p.n_c[b]
        pn = p.neg[b]
        for j in range(blk_eff * Kq):
            if pn[j] >= hot:
                assert p.uc[b][p.cn_pos[b][j]] == pn[j]
            else:
                assert p.cn_pos[b][j] >= p.n_c[b]
        w_sets.append(set(gw.tolist()))
        c_sets.append(set(gc.tolist()))

    # hazards are exactly the windowed intersections of COLD rows — a
    # hot row shared between adjacent blocks must not flag (it never
    # moves over DMA)
    for b in range(nblocks):
        expect = any((w_sets[b] & w_sets[b - m]) or (c_sets[b] & c_sets[b - m])
                     for m in range(1, min(ring_depth, b + 1)))
        assert bool(p.hazard[b]) == expect, (b, p.hazard)

    # the resolved schedule stays safe for the actual cold row sets
    # (tests/ is on sys.path under pytest's prepend import mode)
    from test_fused_pipe import _check_schedule
    row_sets = [{("w", r) for r in w_sets[b]} | {("c", r) for r in c_sets[b]}
                for b in range(nblocks)]
    _check_schedule(resolve_schedule(p.hazard, ring_depth), nblocks,
                    row_sets, p.hazard, ring_depth)


def test_tier_routing_drops_hot_rows_from_dma_lists():
    V, blk, hot = 50, 4, 10
    rng = np.random.default_rng(0)
    c = rng.integers(0, V, 16).astype(np.int32)
    x = rng.integers(0, V, 16).astype(np.int32)
    n = rng.integers(0, V, (16, 3)).astype(np.int32)
    _assert_tier_routing_invariants(c, x, n, V, blk, hot)


def test_tier_routing_hot_overlap_is_not_a_hazard():
    """Adjacent blocks sharing only a HOT row must not set the hazard
    flag: the row lives in VMEM for the whole step, no DMA to order."""
    V, blk, hot = 100, 2, 5
    c = np.array([1, 2, 1, 9], np.int32)      # blocks share hot row 1
    x = np.array([50, 51, 52, 53], np.int32)
    n = np.arange(4, dtype=np.int32).reshape(4, 1) + 60
    p = _plan(c, x, n, V, blk, hot_rows=hot)
    np.testing.assert_array_equal(p.hazard, [0, 0])
    # the same stream with the shared row COLD does flag
    p0 = _plan(c, x, n, V, blk, hot_rows=0)
    np.testing.assert_array_equal(p0.hazard, [0, 1])


def test_tier_routing_extremes_match_pipe_and_empty():
    """hot_rows=0 reproduces the pure-pipe plan exactly; hot_rows=V
    empties every gather list and clears every hazard."""
    V, blk = 30, 4
    rng = np.random.default_rng(3)
    c = rng.integers(0, V, 21).astype(np.int32)
    x = rng.integers(0, V, 21).astype(np.int32)
    n = rng.integers(0, V, (21, 2)).astype(np.int32)
    p0 = _plan(c, x, n, V, blk, hot_rows=0)
    pp = _plan(c, x, n, V, blk)
    for a, b in zip(p0, pp):
        np.testing.assert_array_equal(a, b)
    pv = _plan(c, x, n, V, blk, hot_rows=V)
    assert (pv.n_w == 0).all() and (pv.n_c == 0).all()
    assert (pv.uw == V).all() and (pv.uc == V).all()
    assert (pv.hazard == 0).all()


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
    def test_tier_routing_invariants_on_adversarial_streams(
            data, V, Bq, Kq, blk, rd):
        """For ANY pair stream and ANY hot set size: (hot ∪ cold)
        routing covers every touched row exactly once, the cold-side
        dedup/hazard invariants hold, hot rows never appear in the
        gather/scatter lists."""
        hot = data.draw(st.integers(0, V))
        ids = st.integers(0, V - 1)
        c = np.array(data.draw(st.lists(ids, min_size=Bq, max_size=Bq)),
                     np.int32)
        x = np.array(data.draw(st.lists(ids, min_size=Bq, max_size=Bq)),
                     np.int32)
        n = np.array(data.draw(st.lists(
            st.lists(ids, min_size=Kq, max_size=Kq),
            min_size=Bq, max_size=Bq)), np.int32)
        _assert_tier_routing_invariants(c, x, n, V, blk, hot, ring_depth=rd)


# ------------------------------------------------------------- equivalence
@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    params = {
        "W": jnp.asarray(0.01 * rng.normal(size=(V_BIG, D_BIG)), jnp.float32),
        "C": jnp.asarray(0.01 * rng.normal(size=(V_BIG, D_BIG)), jnp.float32),
    }
    # Zipfian center/context stream: the hot prefix is genuinely hot,
    # and duplicates within and across blocks exercise both tiers'
    # accumulation order
    c = jnp.asarray(np.minimum(rng.zipf(1.2, B) - 1, V_BIG - 1)
                    .astype(np.int32))
    x = jnp.asarray(np.minimum(rng.zipf(1.2, B) - 1, V_BIG - 1)
                    .astype(np.int32))
    c = c.at[1].set(c[0])
    x = x.at[3].set(x[2])
    counts = rng.zipf(1.3, V_BIG).astype(np.float64)
    table = build_noise_table(counts, kind="alias")
    return params, c, x, table


def _sparse_blocked(params, c, x, ids, lr, blk):
    """The reference: one jitted sparse step per pair block (a single
    plain sparse step when one block covers the batch), and the batch
    mean of the blocks' losses."""
    step = jax.jit(sgns.train_step_sparse)
    params = jax.tree.map(jnp.copy, params)
    loss_sum = 0.0
    for b0 in range(0, c.shape[0], blk):
        params, loss = step(params, c[b0:b0 + blk], x[b0:b0 + blk],
                            ids[b0:b0 + blk], lr)
        loss_sum += float(loss) * c[b0:b0 + blk].shape[0]
    return params, loss_sum / c.shape[0]


@pytest.mark.parametrize("hot,blk,ring", [
    # a non-dividing block (64 = 40 + a padded tail of 24) at every hot
    # fraction: pure DMA, a small non-aligned hot set, pure-resident
    (0, 40, 2), (257, 40, 2), (V_BIG, 40, 2),
    # four blocks, two ring depths: hazards and the deeper look-behind
    (0, 16, 2), (0, 16, 3), (257, 16, 2), (257, 16, 3), (V_BIG, 16, 2),
    # one block covering the whole batch
    (257, B, 2),
])
def test_tiered_bit_identical_to_per_block_sparse(world, hot, blk, ring):
    """Past the VMEM envelope: the kernel's step ≡ the per-block sparse
    reference on the replayed negatives, bit for bit, at every hot
    fraction from pure DMA to pure-resident, block size and ring
    depth."""
    params, c, x, table = world
    key = jax.random.PRNGKey(11)
    lr = jnp.float32(0.025)
    pt, loss = fused_pipe_step(
        jax.tree.map(jnp.copy, params), c, x, table, key, lr,
        negatives=K, block_pairs=blk, ring_depth=ring, hot_rows=hot,
        interpret=True)
    ids = fused_negative_ids(_as_seed(key), table["prob"], table["alias"],
                             (B, K))
    pr, ref_loss = _sparse_blocked(params, c, x, ids, lr, blk)
    np.testing.assert_array_equal(np.asarray(pt["W"]), np.asarray(pr["W"]))
    np.testing.assert_array_equal(np.asarray(pt["C"]), np.asarray(pr["C"]))
    assert float(loss) == pytest.approx(ref_loss, rel=1e-5)


# ------------------------------------------------------------ engine wiring
def test_engine_fields_and_registry():
    eng = get_engine("pallas_fused_tiered")
    assert isinstance(eng, FusedTieredPallasEngine)
    assert FusedTieredPallasEngine.__bases__ == (UpdateEngine,)
    assert eng.table_kind == "alias"
    assert eng.describe() == "pallas_fused_tiered:alias"
    assert (eng.block_pairs, eng.ring_depth, eng.hot_rows) == (256, 2, 256)
    assert not hasattr(eng, "sequential")
    assert get_engine("pallas_fused_tiered", block_pairs=64).block_pairs == 64
    assert get_engine("pallas_fused_tiered", hot_rows=1024).hot_rows == 1024
    assert get_engine("pallas_fused_tiered", hot_rows=0).hot_rows == 0
    assert get_engine("pallas_fused_tiered", ring_depth=4).ring_depth == 4
    assert get_engine(eng, ring_depth=3).ring_depth == 3
    with pytest.raises(ValueError, match="alias"):
        get_engine("pallas_fused_tiered:cdf")
    with pytest.raises(ValueError, match="hot_rows"):
        get_engine("pallas_fused_tiered", hot_rows=-1)
    with pytest.raises(ValueError, match="ring_depth"):
        get_engine("pallas_fused_tiered", ring_depth=1)
    with pytest.raises(ValueError, match="block_pairs"):
        get_engine("pallas_fused_tiered", block_pairs=0)


@pytest.mark.parametrize("hot_rows", [0, 8])
def test_trainer_epoch_trains_with_tiered_engine(hot_rows):
    """AsyncShardTrainer (vmap backend, scan over steps) runs the fused
    engine end to end, without and with its hot tier, and the loss
    drops below the init plateau — the wiring the driver and CLIs sit
    on."""
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
                           engine=get_engine("pallas_fused_tiered",
                                             block_pairs=16,
                                             hot_rows=hot_rows))
    p = tr.init(jax.random.PRNGKey(0))
    p, losses = tr.epoch(p, c, x, table, jax.random.PRNGKey(4))
    assert np.isfinite(np.asarray(losses)).all()
    assert float(losses[:, -1].mean()) < (cfg.negatives + 1) * np.log(2)
