"""The graph input path (``repro.data.graph``) and its set-up
(``prepare_graph_training``): the CSR, the degree order, the seeded
generator, the walk streams through the multi-host plan, and the
epoch schedule from the walk count."""

import numpy as np
import pytest

from repro import obs
from repro.core.driver import prepare_graph_training, train_prepared
from repro.core.schedule import plan_epoch
from repro.core.sgns import SGNSConfig
from repro.data.graph import (Graph, WalkStream, chung_lu_edges,
                              expected_walk_pairs, make_walk_streams,
                              random_walks, relabel_by_degree)
from repro.data.pipeline import HostShardPlan, PairChunkStream

SEED = 2 ** 31 + 17
N_NODES, N_EDGES = 2000, 5000
CHUNK_KW = dict(batch_size=32, steps_per_chunk=4, sentences_per_block=16)


@pytest.fixture(scope="module")
def graph():
    src, dst = chung_lu_edges(SEED, N_NODES, N_EDGES, 200)
    return Graph.from_edges(src, dst, N_NODES)


@pytest.fixture(scope="module")
def ordered(graph):
    return relabel_by_degree(graph)[0]


def _edge_set(g):
    u, v = g.edges()
    return set(zip(u.tolist(), v.tolist()))


# ------------------------------------------------------------ the graph
def test_generator_gives_the_edges_asked_for_and_no_node_alone():
    src, dst = chung_lu_edges(SEED, N_NODES, N_EDGES, 200)
    assert len(src) == N_EDGES and np.all(src != dst)
    keys = np.minimum(src, dst) * N_NODES + np.maximum(src, dst)
    assert len(np.unique(keys)) == N_EDGES
    g = Graph.from_edges(src, dst, N_NODES)
    assert g.num_edges == N_EDGES and g.degrees.min() >= 1
    assert 100 <= g.degrees.max() <= 200
    again = chung_lu_edges(SEED, N_NODES, N_EDGES, 200)
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])
    other = chung_lu_edges(SEED + 1, N_NODES, N_EDGES, 200)
    assert not np.array_equal(src[:100], other[0][:100])


def test_csr_is_the_simple_graph_of_the_edge_list(tmp_path):
    g = Graph.from_edges([0, 1, 1, 2, 3, 3], [1, 0, 2, 2, 0, 1], 5)
    assert _edge_set(g) == {(0, 1), (1, 2), (0, 3), (1, 3)}
    assert g.degrees.tolist() == [2, 3, 1, 2, 0]
    assert g.indices[g.indptr[1]:g.indptr[2]].tolist() == [0, 2, 3]
    g.save(tmp_path / "g.npz")
    back = Graph.load(tmp_path / "g.npz")
    assert np.array_equal(back.indptr, g.indptr)
    assert np.array_equal(back.indices, g.indices)
    with pytest.raises(ValueError):
        Graph.from_edges([0], [5], 5)


def test_relabel_by_degree_is_a_permutation_to_non_increasing_degree(graph):
    ordered, order = relabel_by_degree(graph)
    assert sorted(order.tolist()) == list(range(N_NODES))
    assert np.all(np.diff(ordered.degrees) <= 0)
    assert np.array_equal(ordered.degrees, graph.degrees[order])
    # ties keep node order, and the edges are the same edges renamed
    deg = graph.degrees[order]
    assert np.all(np.diff(order)[np.diff(deg) == 0] > 0)
    rank = np.empty(N_NODES, np.int64)
    rank[order] = np.arange(N_NODES)
    renamed = {tuple(sorted((int(rank[u]), int(rank[v]))))
               for u, v in _edge_set(graph)}
    assert renamed == _edge_set(ordered)


# ------------------------------------------------------------ the walks
def test_every_walk_step_is_an_edge(ordered):
    starts = np.arange(0, N_NODES, 3, dtype=np.int32)
    walks = random_walks(ordered, starts, 40, np.random.default_rng(1))
    assert walks.shape == (len(starts), 40)
    assert np.array_equal(walks[:, 0], starts)
    edges = _edge_set(ordered)
    a, b = walks[:, :-1].ravel(), walks[:, 1:].ravel()
    assert all((min(u, v), max(u, v)) in edges
               for u, v in zip(a.tolist(), b.tolist()))


def test_walk_pairs_per_walk_match_their_expectation(ordered):
    assert expected_walk_pairs(40, 10) == 396.0
    s = WalkStream(ordered, worker=0, rate=0.5, num_workers=2)
    pairs = sum(len(c) for c, _ in s.pair_blocks(0, 256))
    assert pairs / s.num_walks == pytest.approx(396.0, rel=0.01)


def test_block_streams_are_deterministic_and_differ_by_worker_epoch_block(
        ordered):
    def blocks(worker, epoch):
        s = WalkStream(ordered, worker=worker, rate=0.05, num_workers=4,
                       seed=SEED)
        return [c for c, _ in s.pair_blocks(epoch, 64)]

    a, b = blocks(0, 0), blocks(0, 0)
    assert len(a) == -(-int(round(0.05 * 80 * N_NODES)) // 64)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], a[1])                  # across blocks
    assert not np.array_equal(a[0], blocks(1, 0)[0])       # across workers
    assert not np.array_equal(a[0], blocks(0, 1)[0])       # across epochs


def test_walk_source_spans_and_counts_its_blocks(ordered):
    obs.reset()
    try:
        s = WalkStream(ordered, worker=0, rate=0.01, num_workers=1)
        cut = sum(len(c) for c, _ in s.pair_blocks(0, 64))
        assert obs.read(obs.GRAPH_PAIRS) == cut
        assert obs.read(obs.WALK_NS) > 0
    finally:
        obs.reset()
    assert obs.INGEST_WALK.startswith("repro.")


@pytest.mark.parametrize("process_count", [1, 2, 3])
def test_host_plan_concatenation_equals_the_single_host_walk_stream(
        ordered, process_count):
    streams = make_walk_streams(ordered, 6, 0.01, window=5, seed=SEED)
    whole = PairChunkStream(streams, **CHUNK_KW).chunks(0, 30)
    parts = [p.chunk_stream(streams, **CHUNK_KW).chunks(0, 30)
             for p in HostShardPlan.all_hosts(process_count, 6)]
    for chunk, *local in zip(whole, *parts):
        for k in range(2):
            assert np.array_equal(chunk[k],
                                  np.concatenate([p[k] for p in local]))


# ------------------------------------------------------------ the set-up
def test_graph_setup_is_the_node_set_in_degree_order(graph):
    setup = prepare_graph_training(
        graph, 4, SGNSConfig(0, dim=8, window=10), rate=0.05, batch_size=64,
        steps_per_chunk=8, seed=SEED, engine="sparse:alias",
        process_index=1, process_count=2)
    ordered, order = relabel_by_degree(graph)
    assert setup.cfg.vocab_size == N_NODES
    assert np.array_equal(setup.union_vocab.word_ids, order)
    assert np.array_equal(setup.union_vocab.counts, ordered.degrees)
    assert setup.mask.shape == (4, N_NODES) and setup.mask.all()
    prob = np.asarray(setup.neg_table["prob"])
    assert prob.shape == (4, N_NODES) and np.all(prob == prob[0])
    walks = int(round(0.05 * 80 * N_NODES))
    assert [s.num_walks for s in setup.streams] == [walks] * 4
    assert setup.sched == plan_epoch(396 * walks, 64, 1, 8)  # no walk drawn
    assert list(setup.plan.workers) == [2, 3]


def test_graph_setup_trains_through_the_common_loop(graph):
    setup = prepare_graph_training(
        graph, 2, SGNSConfig(0, dim=8, window=5), rate=0.001, batch_size=64,
        steps_per_chunk=8, walk_length=10, seed=SEED, engine="sparse:alias")
    res = train_prepared(setup)
    assert res.timings["steps_per_epoch"] == setup.sched.steps_per_epoch
    assert res.stacked.models.shape == (2, N_NODES, 8)
    assert len(res.losses) == 1 and np.isfinite(res.losses[0])
