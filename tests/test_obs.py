"""The program's tracing (``repro.obs``): counters, the rows-moved count
inside the step, the device scopes in the epoch's HLO and the ingestion
spans in a profiler trace. CPU only: the kernels run interpreted."""

import pathlib
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.async_trainer import (AsyncShardTrainer, converting,
                                     make_worker_epoch)
from repro.core.engine import get_engine
from repro.core.sgns import SGNSConfig
from repro.data.pairs import build_noise_table
from repro.kernels.alias_draw import _as_seed, fused_negative_ids
from repro.kernels.sgns_fused_pipe import (plan_blocks, plan_row_traffic,
                                           row_traffic)

N, V, D, S, B, BLK = 2, 300, 16, 3, 48, 16


@pytest.fixture(autouse=True)
def fresh_counters():
    obs.reset()
    yield
    obs.reset()


def _trainer(engine):
    cfg = SGNSConfig(vocab_size=V, dim=D, negatives=4)
    return AsyncShardTrainer(cfg=cfg, num_workers=N, total_steps=100,
                             engine=engine)


def _chunk(seed=0):
    rng = np.random.default_rng(seed)
    ids = (V * rng.power(0.3, (2, N, S, B))).astype(np.int32)  # Zipf-like
    counts = np.bincount(ids.ravel(), minlength=V) + 1.0
    table = jax.tree.map(lambda a: jnp.stack([a] * N),
                         build_noise_table(counts, kind="alias"))
    return jnp.asarray(ids[0]), jnp.asarray(ids[1]), table


# ------------------------------------------------------------ counters
def test_counters_sum_host_ints_and_device_arrays():
    obs.count("a", 3)
    obs.count("a", jnp.asarray([4, 5], jnp.int32))
    obs.count("a", jnp.int32(2), times=10)
    obs.count("b", 7, times=2)
    assert obs.read("a") == 3 + 9 + 20
    assert obs.read("b") == 14
    assert obs.read("never") == 0
    obs.reset()
    assert obs.read("a") == 0


def test_device_values_fold_on_the_device_without_overflow(monkeypatch):
    monkeypatch.setattr(obs, "FOLD_EVERY", 4)
    big = 2 ** 31 - 1
    for _ in range(10):                     # two folds and two pending
        obs.count("big", jnp.asarray([big, 1], jnp.int32), times=3)
    assert sum(len(v) for v in obs._pending.values()) == 2
    assert obs.read("big") == 3 * 10 * (big + 1)


def test_counters_lose_no_update_across_threads(monkeypatch):
    """Host and device counts from more threads than cores, with folds
    racing the appends and the thread switch interval shortened."""
    monkeypatch.setattr(obs, "FOLD_EVERY", 8)
    one = jnp.ones((), jnp.int32)

    def work(value):
        for _ in range(300):
            obs.count("t", value)

    threads = [threading.Thread(target=work, args=(v,))
               for v in [1, one] * 8]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert obs.read("t") == 16 * 300


def test_names_of_spans_are_the_programs_own():
    spans = [obs.INGEST_EXTRACT, obs.INGEST_H2D, obs.INGEST_WAIT,
             obs.TRAIN_DISPATCH]
    assert all(s.startswith("repro.") for s in spans)
    with obs.span(obs.TRAIN_DISPATCH, chunk=3):     # not tracing: a no-op
        pass


# ------------------------------------------------------------ the step's count
@pytest.mark.parametrize("hot_rows", [0, 24])
def test_rows_moved_is_the_plan_row_traffic_of_every_step(hot_rows):
    """``sgns.rows_moved`` over one chunk is the sum over its steps of
    ``plan_row_traffic`` recomputed from each step's own plan (the keys
    split as the epoch splits them)."""
    eng = get_engine("pallas_fused_tiered", block_pairs=BLK,
                     hot_rows=hot_rows)
    tr = _trainer(eng)
    c, x, table = _chunk()
    key = jax.random.PRNGKey(7)
    tr.epoch(tr.init(jax.random.PRNGKey(0)), c, x, table, key, step0=0)

    expect = 0
    for w, wkey in enumerate(jax.random.split(key, N)):
        for s in range(S):
            wkey, sub = jax.random.split(wkey)
            negs = fused_negative_ids(_as_seed(sub), table["prob"][w],
                                      table["alias"][w], (B, 4))
            plan = plan_blocks(c[w, s], x[w, s], negs, V, BLK,
                               hot_rows=hot_rows, ring_depth=2)
            expect += plan_row_traffic(plan, hot_rows=hot_rows)
    assert obs.read(obs.ROWS_MOVED) == expect
    assert obs.read(obs.PAIRS) == N * S * B
    # interpret mode: a row is d float32 wide
    assert obs.read(obs.BYTES_MOVED) == expect * 4 * D


@pytest.mark.parametrize("hot_rows", [0, 24])
def test_hot_touches_is_a_numpy_recount_of_every_step(hot_rows):
    """``sgns.hot_touches`` over one chunk is the count, in NumPy, of each
    step's centres, contexts and replayed negatives with id < hot_rows;
    0 without a hot tier."""
    eng = get_engine("pallas_fused_tiered", block_pairs=BLK,
                     hot_rows=hot_rows)
    tr = _trainer(eng)
    c, x, table = _chunk(4)
    key = jax.random.PRNGKey(11)
    tr.epoch(tr.init(jax.random.PRNGKey(0)), c, x, table, key, step0=0)

    expect = 0
    for w, wkey in enumerate(jax.random.split(key, N)):
        for s in range(S):
            wkey, sub = jax.random.split(wkey)
            negs = fused_negative_ids(_as_seed(sub), table["prob"][w],
                                      table["alias"][w], (B, 4))
            for ids in (c[w, s], x[w, s], negs):
                expect += int(np.sum(np.asarray(ids) < hot_rows))
    assert obs.read(obs.HOT_TOUCHES) == expect
    assert (expect > 0) == (hot_rows > 0)


def test_row_traffic_is_one_definition():
    c, x, table = _chunk(1)
    negs = fused_negative_ids(_as_seed(jax.random.PRNGKey(1)),
                              table["prob"][0], table["alias"][0], (B, 4))
    plan = plan_blocks(c[0, 0], x[0, 0], negs, V, BLK, hot_rows=8)
    by_hand = 2 * int(plan.n_w.sum() + plan.n_c.sum()) + 4 * 8
    assert plan_row_traffic(plan, hot_rows=8) == by_hand
    assert jax.jit(row_traffic, static_argnums=1)(plan, 8) == by_hand


@pytest.mark.parametrize("engine,hot_rows", [
    ("sparse:alias", None), ("pallas_fused_tiered", 0),
    ("pallas_fused_tiered", 256)])
def test_epoch_returns_params_and_losses_of_the_steps(engine, hot_rows):
    """``epoch`` still returns ``(params, losses)``, bit-identical to the
    engine's own step scanned per worker under vmap (the epoch as it was
    before it counted rows); an engine without a DMA kernel counts no
    rows."""
    eng = get_engine(engine, **({} if hot_rows is None
                                else {"block_pairs": BLK,
                                      "hot_rows": hot_rows}))
    tr = _trainer(eng)
    c, x, table = _chunk(2)
    p0 = tr.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(3)
    out = tr.epoch(jax.tree.map(jnp.copy, p0), c, x, table, key, step0=5)
    assert isinstance(out, tuple) and len(out) == 2
    step = eng.make_step(tr.cfg, tr.total_steps)

    def steps(params, centers, contexts, tab, wkey, step0):
        def body(carry, xs):
            p, k, i = carry
            k, sub = jax.random.split(k)
            p, loss = step(p, xs[0], xs[1], tab, sub, step0 + i)
            return (p, k, i + 1), loss
        (params, _, _), losses = jax.lax.scan(
            body, (params, wkey, jnp.int32(0)), (centers, contexts))
        return params, losses

    ref = jax.jit(jax.vmap(steps))(p0, c, x, table, jax.random.split(key, N),
                                   jnp.full((N,), 5, jnp.int32))
    for t in ("W", "C"):
        np.testing.assert_array_equal(np.asarray(out[0][t]),
                                      np.asarray(ref[0][t]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(ref[1]))
    rows = obs.read(obs.ROWS_MOVED)
    assert (rows == 0) == engine.startswith("sparse")
    # the hot-tier counter rides the same epoch, which stays bit-identical
    assert (obs.read(obs.HOT_TOUCHES) > 0) == bool(hot_rows)


# ------------------------------------------------ the tables across chunks
@pytest.mark.parametrize("engine", ["sparse:alias", "pallas_fused_tiered"])
def test_tables_stay_packed_across_chunks_and_convert_on_read(engine):
    """Three chunks through ``epoch``, W and C read after the first:
    tables, losses and step counters bit-identical to the program that
    converts around every chunk; one pack before chunk 1, one unpack at
    the read, one pack before chunk 2, none before chunk 3 (none at all
    where the engine's layout is the plain one); ``block_until_ready``
    waits for the tables themselves and converts nothing."""
    tiered = engine == "pallas_fused_tiered"
    eng = get_engine(engine, **({"block_pairs": BLK, "hot_rows": 24}
                                if tiered else {}))
    tr = _trainer(eng)
    p0 = tr.init(jax.random.PRNGKey(0))
    every_chunk = jax.jit(jax.vmap(converting(
        make_worker_epoch(tr.cfg, tr.total_steps, eng), eng, tr.cfg)))
    params, ref = jax.tree.map(jnp.copy, p0), p0
    ref_counts = {n: 0 for n in obs.STEP_COUNTERS}
    relayouts = []
    for k in range(3):
        c, x, table = _chunk(k)
        key = jax.random.PRNGKey(10 + k)
        params, losses = tr.epoch(params, c, x, table, key, step0=k * S)
        relayouts.append(obs.read(obs.RELAYOUTS))
        ref, ref_losses, counts = every_chunk(
            ref, c, x, table, jax.random.split(key, N),
            jnp.full((N,), k * S, jnp.int32))
        ref_counts = {n: ref_counts[n] + int(counts[n].sum())
                      for n in ref_counts}
        np.testing.assert_array_equal(np.asarray(losses),
                                      np.asarray(ref_losses))
        if k == 0:
            for t in ("W", "C"):
                np.testing.assert_array_equal(np.asarray(params[t]),
                                              np.asarray(ref[t]))
            # converted in place: the leaves are the (n, V, d) tables now
            assert [a.shape for a in jax.tree.leaves(params)] == [(N, V, D)] * 2
            relayouts.append(obs.read(obs.RELAYOUTS))
    assert relayouts == ([1, 2, 3, 3] if tiered else [0] * 4)

    leaves = jax.tree.leaves(params)
    assert jax.block_until_ready(params) is params
    assert all(a.is_ready() for a in leaves)
    assert [a.shape for a in leaves] == [(N, V, 1, D) if tiered
                                         else (N, V, D)] * 2
    assert obs.read(obs.RELAYOUTS) == relayouts[-1]
    for t in ("W", "C"):
        np.testing.assert_array_equal(np.asarray(params[t]),
                                      np.asarray(ref[t]))
    for n in obs.STEP_COUNTERS:
        assert obs.read(n) == ref_counts[n]
    assert (ref_counts[obs.ROWS_MOVED] > 0) == tiered


# ------------------------------------------------------------ device scopes
def test_epoch_hlo_names_the_planner_and_the_layout_conversion():
    """The lowered epoch's op names (its ``loc`` names, which become the
    HLO ops' ``op_name`` metadata) carry the planner's scope, and the
    kernel is not under it; the epoch converts no layout, and both
    stand-alone conversions carry the layout scope."""
    tr = _trainer(get_engine("pallas_fused_tiered", block_pairs=BLK,
                             hot_rows=24))
    c, x, table = _chunk()
    params = tr.init(jax.random.PRNGKey(0))
    packed = {k: tr.layout.pack(v) for k, v in params.items()}
    keys = jax.random.split(jax.random.PRNGKey(1), N)
    text = tr._jit_epoch().lower(packed, c, x, table, keys,
                                 jnp.zeros((N,), jnp.int32)).as_text(
        debug_info=True)
    names = lambda text: set(re.findall(r'loc\("([^"]+)"', text))
    op_names = names(text)
    assert any(obs.PLAN_SCOPE in n and "sort" in n for n in op_names)
    assert not any(obs.LAYOUT_SCOPE in n for n in op_names)
    assert any(obs.SGNS_KERNEL in n for n in op_names)
    assert not any(obs.SGNS_KERNEL in n and obs.PLAN_SCOPE in n
                   for n in op_names)
    for convert, table in ((tr.layout.pack, params["W"]),
                           (tr.layout.unpack, packed["W"])):
        text = convert.lower(table).as_text(debug_info=True)
        assert any(obs.LAYOUT_SCOPE in n for n in names(text))


# ------------------------------------------------------------ host spans
def test_ingestion_spans_carry_their_chunk_in_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    from repro.data.corpus import SemanticCorpusModel
    from repro.data.pipeline import (PairChunkStream, make_worker_streams,
                                     prefetch_chunks)
    from repro.data.vocab import build_vocab

    corpus = SemanticCorpusModel.create(vocab_size=200, seed=0).generate(
        num_sentences=300, seed=1)
    vocab = build_vocab(corpus, 200, min_count=1, max_size=None)
    stream = PairChunkStream(
        make_worker_streams(corpus, vocab, num_workers=2, strategy="shuffle",
                            window=3, seed=2),
        batch_size=16, steps_per_chunk=2, sentences_per_block=64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = list(prefetch_chunks(stream.chunks(0, 2), depth=1))
    finally:
        jax.profiler.stop_trace()
    assert len(got) == 2
    f = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    seen = {}
    for plane in ProfileData.from_file(str(f)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    seen.setdefault(e.name, set()).add(dict(e.stats)["chunk"])
    assert seen[obs.INGEST_EXTRACT] == {0, 1}
    assert seen[obs.INGEST_H2D] == {0, 1}
    assert {0, 1} <= seen[obs.INGEST_WAIT]      # and the sentinel's wait
