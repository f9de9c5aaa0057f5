"""Update-engine abstraction: registry, cross-engine equivalence, the
fused engine's counter-PRNG negative draw (chi-square, determinism),
and the zero-collective property of every async engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sgns
from repro.core.engine import (
    ENGINE_NAMES, FusedTieredPallasEngine, UpdateEngine, get_engine)
from repro.core.sgns import SGNSConfig
from repro.data.pairs import build_noise_table, unigram_noise_probs
from repro.kernels.alias_draw import _as_seed, fused_negative_ids


def _zipf_counts(V, seed=0):
    return np.random.default_rng(seed).zipf(1.3, V).astype(np.float64)


@pytest.fixture(scope="module")
def cfg():
    return SGNSConfig(vocab_size=150, dim=32, negatives=4)


@pytest.fixture(scope="module")
def batch(cfg):
    rng = np.random.default_rng(2)
    B = 48
    c = jnp.asarray(rng.integers(0, cfg.vocab_size, B, dtype=np.int32))
    x = jnp.asarray(rng.integers(0, cfg.vocab_size, B, dtype=np.int32))
    return c, x


@pytest.fixture(scope="module")
def tables(cfg):
    counts = _zipf_counts(cfg.vocab_size)
    return {kind: build_noise_table(counts, kind=kind)
            for kind in ("cdf", "alias")}, counts


def _params(cfg, seed=1):
    p = sgns.init_params(jax.random.PRNGKey(seed), cfg)
    return {"W": p["W"], "C": 0.02 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), p["C"].shape)}


# ------------------------------------------------------------------ registry
def test_registry_resolves_all_names():
    assert ENGINE_NAMES == ("dense", "sparse", "pallas", "pallas_fused_tiered")
    for name in ENGINE_NAMES:
        eng = get_engine(name)
        assert isinstance(eng, UpdateEngine)
        assert eng.name == name
        assert eng.table_kind in ("cdf", "alias")


def test_registry_sampler_suffix_and_overrides():
    assert get_engine("sparse:alias").sampler == "alias"
    assert get_engine("pallas:cdf").table_kind == "cdf"
    assert get_engine("dense", sampler="alias").table_kind == "alias"
    eng = get_engine("sparse")
    assert get_engine(eng) is eng                      # instance passthrough
    assert get_engine(eng, sampler="alias").sampler == "alias"


def test_registry_rejects_unknown():
    with pytest.raises(ValueError, match="unknown update engine"):
        get_engine("hogwild")


def test_fused_engine_is_alias_only():
    assert FusedTieredPallasEngine().table_kind == "alias"
    with pytest.raises(ValueError, match="alias"):
        get_engine("pallas_fused_tiered:cdf")


def test_engines_hashable_and_value_equal():
    assert get_engine("sparse:alias") == get_engine("sparse:alias")
    assert hash(get_engine("pallas")) == hash(get_engine("pallas"))
    assert get_engine("sparse") != get_engine("sparse:alias")


# ------------------------------------------------------- dial validation
def test_engine_rejects_bad_dials_at_construction():
    """Shape-free dial errors surface at get_engine time with a clear
    message, not as a cryptic kernel failure mid-epoch."""
    with pytest.raises(ValueError, match="ring_depth >= 2"):
        get_engine("pallas_fused_tiered", ring_depth=1)
    with pytest.raises(ValueError, match="ring_depth >= 2"):
        get_engine("pallas_fused_tiered", ring_depth=0)
    with pytest.raises(ValueError, match="hot_rows >= 0"):
        get_engine("pallas_fused_tiered", hot_rows=-1)
    with pytest.raises(ValueError, match="block_pairs >= 1"):
        get_engine("pallas_fused_tiered", block_pairs=0)
    with pytest.raises(ValueError, match="block_pairs >= 1"):
        get_engine("pallas_fused_tiered", block_pairs=-3)


def test_trainer_rejects_hot_tier_larger_than_vocab(cfg):
    """hot_rows > V is a misconfiguration the trainer rejects at
    construction (engine.validate); hot_rows == V (pure-resident) is
    legal."""
    from repro.core.async_trainer import AsyncShardTrainer

    with pytest.raises(ValueError, match="exceeds"):
        AsyncShardTrainer(
            cfg=cfg, num_workers=1, total_steps=4,
            engine=get_engine("pallas_fused_tiered",
                              hot_rows=cfg.vocab_size + 1))
    tr = AsyncShardTrainer(
        cfg=cfg, num_workers=1, total_steps=4,
        engine=get_engine("pallas_fused_tiered",
                          hot_rows=cfg.vocab_size))
    assert tr.engine.hot_rows == cfg.vocab_size


# -------------------------------------------------------------- equivalence
def test_dense_sparse_pallas_steps_identical(cfg, batch, tables):
    """Same key ⇒ same negatives ⇒ dense ≡ sparse ≡ pallas losses and
    params (autodiff vs manual row grads vs the Pallas tile kernel)."""
    tabs, _ = tables
    c, x = batch
    key = jax.random.PRNGKey(7)
    outs = {}
    for name in ("dense", "sparse", "pallas"):
        step = get_engine(name).make_step(cfg, total_steps=100)
        p, loss = step(_params(cfg), c, x, tabs["cdf"], key, jnp.int32(3))
        outs[name] = (p, float(loss))
    for name in ("sparse", "pallas"):
        np.testing.assert_allclose(outs[name][1], outs["dense"][1], rtol=1e-5)
        np.testing.assert_allclose(outs[name][0]["W"], outs["dense"][0]["W"],
                                   atol=1e-5)
        np.testing.assert_allclose(outs[name][0]["C"], outs["dense"][0]["C"],
                                   atol=1e-5)


def test_all_engines_converge_through_trainer(cfg, tables):
    """Whole-epoch equivalence up to sampling seed: every engine trains
    the same data to a loss below the (k+1)·log2 init plateau, and the
    deterministic trio agrees exactly."""
    from repro.core.async_trainer import AsyncShardTrainer

    tabs, counts = tables
    n, S, B = 2, 12, 64
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.integers(0, 30, (n, S, B)), jnp.int32)
    x = jnp.asarray((np.asarray(c) + 1) % 30, jnp.int32)   # structured
    losses = {}
    for name in ENGINE_NAMES:
        # fit the tiered hot prefix inside the 150-word test vocab (the
        # trainer rejects hot_rows > V at construction)
        eng = get_engine(name, hot_rows=64) \
            if name == "pallas_fused_tiered" else name
        tr = AsyncShardTrainer(cfg=cfg, num_workers=n, total_steps=S,
                               engine=eng)
        table = jax.tree.map(lambda a: jnp.stack([a, a]),
                             tabs[tr.engine.table_kind])
        p = tr.init(jax.random.PRNGKey(0))
        p, ls = tr.epoch(p, c, x, table, jax.random.PRNGKey(4))
        assert np.isfinite(np.asarray(ls)).all(), name
        losses[name] = np.asarray(ls)
        # learning happened: final loss under the all-zero-C plateau
        assert float(ls[:, -1].mean()) < (cfg.negatives + 1) * np.log(2), name
    np.testing.assert_allclose(losses["sparse"], losses["dense"], rtol=1e-4)
    np.testing.assert_allclose(losses["pallas"], losses["dense"], rtol=1e-4)
    # fused draws its own negatives: same ballpark, not bitwise
    assert abs(losses["pallas_fused_tiered"].mean()
               - losses["dense"].mean()) < 0.5


# ----------------------------------------------- the fused engine's draw
def _fused_draw(table, key, shape):
    return fused_negative_ids(_as_seed(key), table["prob"], table["alias"],
                              shape)


def test_fused_draw_chi_square_matches_unigram_075(tables):
    """Chi-square goodness-of-fit of the fused engine's counter-PRNG
    draws (``fused_negative_ids``, what its planner replays) against
    unigram^0.75."""
    tabs, counts = tables
    p = unigram_noise_probs(counts)
    N = 400_000
    draws = np.asarray(_fused_draw(tabs["alias"], jax.random.PRNGKey(123),
                                   (N,)))
    assert draws.min() >= 0 and draws.max() < len(p)
    obs = np.bincount(draws, minlength=len(p)).astype(np.float64)
    exp = p * N
    keep = exp >= 5.0                       # classic chi-square validity rule
    chi2 = float(np.sum((obs[keep] - exp[keep]) ** 2 / exp[keep])
                 + (obs[~keep].sum() - exp[~keep].sum()) ** 2
                 / max(exp[~keep].sum(), 1.0))
    df = int(keep.sum())                    # (+1 pooled bin, -1 constraint)
    # ~p=0.001 normal-approx critical value; generous but catches a
    # broken mixer or a biased index draw immediately
    crit = df + 4.0 * np.sqrt(2.0 * df)
    assert chi2 < crit, (chi2, df, crit)


def test_fused_draw_deterministic_and_seed_sensitive(tables):
    tabs, _ = tables
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    a = _fused_draw(tabs["alias"], k1, (64, 5))
    b = _fused_draw(tabs["alias"], k1, (64, 5))
    c = _fused_draw(tabs["alias"], k2, (64, 5))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert a.dtype == jnp.int32


def test_fused_steps_draw_fresh_negatives_each_scan_step(cfg, tables):
    """Across an epoch scan the per-step key split must decorrelate the
    fused draws (a stuck counter/seed would reuse one negative set)."""
    tabs, _ = tables
    ids = [np.asarray(_fused_draw(
        tabs["alias"], jax.random.split(jax.random.PRNGKey(5), 3)[i],
        (16, 4))) for i in range(3)]
    assert not np.array_equal(ids[0], ids[1])
    assert not np.array_equal(ids[1], ids[2])


# --------------------------------------------------------- no collectives
# Every registered engine × every sampler layout it supports (and the
# fused engine with and without its hot tier). The single source of
# truth for the paper's headline property — the per-engine ad-hoc
# checks that used to live in test_system / test_alias are gone.
ASYNC_ENGINE_SPECS = (
    "dense:cdf", "dense:alias",
    "sparse:cdf", "sparse:alias",
    "pallas:cdf", "pallas:alias",
    "pallas_fused_tiered:alias@0",   # alias is its only layout; the
                                     # planner replays the same draw —
                                     # sort and compare, no collectives
    "pallas_fused_tiered:alias@64",  # hot tier is per-worker-private:
                                     # no synchronization to add
)


def _async_engine(spec):
    """``"name:sampler"``, or ``"name:sampler@hot_rows"`` for the fused
    engine."""
    spec, _, hot = spec.partition("@")
    return get_engine(spec, hot_rows=int(hot)) if hot else get_engine(spec)


def test_collective_spec_matrix_covers_registry():
    """A new engine registered without a row here must fail loudly."""
    assert {_async_engine(s).name for s in ASYNC_ENGINE_SPECS} \
        == set(ENGINE_NAMES)


@pytest.mark.parametrize("spec", ASYNC_ENGINE_SPECS)
def test_async_engine_epoch_is_collective_free(cfg, spec):
    """The paper's headline property holds for each engine × sampler,
    certified through ``repro.analysis.contracts`` (the single checker:
    structured op-walk over the lowered epoch + table-donation aliasing
    of the step) — no duplicated regexes in tests."""
    from repro.analysis.contracts import certify_engine_contracts

    eng = _async_engine(spec)
    rep = certify_engine_contracts(
        eng, vocab_size=cfg.vocab_size, dim=cfg.dim,
        negatives=cfg.negatives, steps=4, batch=64)
    assert rep.zero_collective
    assert rep.aliasing.aliased_table_args >= 2, spec


# ----------------------------------------------------- sync epochs speak it
def test_sync_epoch_takes_engine(cfg, tables):
    from repro.core.async_trainer import make_sync_epoch

    tabs, _ = tables
    epoch = make_sync_epoch(cfg, tabs["alias"], total_steps=8,
                            engine="sparse:alias")
    rng = np.random.default_rng(1)
    c = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    p, losses = epoch(sgns.init_params(jax.random.PRNGKey(0), cfg), c, c,
                      jax.random.PRNGKey(1), jnp.int32(0))
    assert losses.shape == (4,)
    assert np.isfinite(np.asarray(losses)).all()


def test_periodic_sync_epoch_runs_engine_steps(cfg, tables):
    from repro.core.async_trainer import make_periodic_sync_epoch

    tabs, _ = tables
    mesh = jax.make_mesh((1,), ("worker",))
    epoch = make_periodic_sync_epoch(cfg, tabs["cdf"], total_steps=8,
                                     sync_every=2, mesh=mesh,
                                     engine="sparse")
    rng = np.random.default_rng(1)
    c = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 2, 32)), jnp.int32)
    p, losses = epoch(sgns.init_params(jax.random.PRNGKey(0), cfg), c, c,
                      jax.random.PRNGKey(1), jnp.int32(0))
    assert losses.shape == (2, 2)
    assert np.isfinite(np.asarray(losses)).all()
