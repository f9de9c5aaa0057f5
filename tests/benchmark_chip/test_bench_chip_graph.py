"""The DeepWalk cell on the CPU: the plain walk reference reproduces the
program's fed ids, the program's tables after the checked chunks meet
the cell's own limits, the control and every fault the cells can have
do not, and the cell's two new readers read what they must."""

import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench_chip_tiny import run_cell
from bench_chip_tiny_graph import tiny_graph_copy
from benchmarks.chip.harness import Outcome, Run
from benchmarks.chip.kinds import train_graph
from benchmarks.chip.reference import walks as ref_walks
from benchmarks.chip.spec import Spec
from benchmarks.chip.traffic.graph import chung_lu_edges
from test_bench_chip_control import (_altered_token, _failed, _half_batch,
                                     _unchanged_state)

SEED = 2 ** 31 + 41


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_graph_copy(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


@pytest.mark.parametrize("process_index", [0, 1])
def test_reference_walk_ids_equal_the_program_stream(process_index):
    """Each held worker's chunks, id for id, past a wrap of its sample."""
    from repro.core.driver import prepare_graph_training
    from repro.core.sgns import SGNSConfig
    from repro.data.graph import Graph

    src, dst = chung_lu_edges(SEED, 1500, 4000, 150)
    kw = dict(rate=0.01, walks_per_node=10, walk_length=40, window=10)
    setup = prepare_graph_training(
        Graph.from_edges(src, dst, 1500), 4, SGNSConfig(0, dim=8),
        batch_size=32, seed=SEED, engine="sparse", steps_per_chunk=4,
        walks_per_block=16, process_index=process_index, process_count=2,
        **kw)
    stream = setup.plan.chunk_stream(setup.streams, batch_size=32,
                                     steps_per_chunk=4, sentences_per_block=16)
    it = stream.chunks(0, None)
    prog = [next(it) for _ in range(500)]     # 64,000 pairs a worker
    g = ref_walks.build(src, dst, 1500)
    held = ref_walks.local_workers(4, process_index, 2)
    assert list(held) == list(setup.plan.workers)
    for i, w in enumerate(held):
        assert len(ref_walks.walk_starts(g, w, 0.01, 10, SEED)) == 150
        c, x = ref_walks.worker_chunks(
            g, w, walks_per_block=16, chunks=500, steps=4, batch=32,
            seed=SEED, **kw)
        assert np.array_equal(c, np.stack([p[0][i] for p in prog]))
        assert np.array_equal(x, np.stack([p[1][i] for p in prog]))
    assert np.array_equal(g.degree, setup.union_vocab.counts)


def test_generator_is_a_copy_of_the_programs():
    from repro.data.graph import chung_lu_edges as program_edges

    for a, b in zip(chung_lu_edges(SEED, 2000, 5000, 200, 2.2),
                    program_edges(SEED, 2000, 5000, 200, 2.2)):
        assert np.array_equal(a, b)


def _run(root, cell="tiny.graph", seed=2 ** 31 + 3):
    spec = Spec(root)
    c = spec.cell(cell)
    return Run(cell=c, config=spec.config(c), traffic=spec.traffic(c),
               seed=seed, seconds=1.0, trace=False,
               devices=jax.devices()[:1], t_start=0.0)


def test_graph_control_bfloat16_tables_is_not_correct(root):
    run = _run(root)
    src, dst = train_graph.make_graph(run)
    ids = train_graph.reference_ids(run, src, dst)
    ref = train_graph.reference_readings(run, ids)
    ctl = train_graph.reference_readings(run, ids, dtype="bfloat16")
    from benchmarks.chip.kinds import train

    assert _failed(train.gaps(ctl, ref), run.traffic["limits"])


@pytest.mark.parametrize("fault", [
    _unchanged_state, _half_batch, _altered_token],
    ids=lambda v: v.__name__)
def test_graph_cell_is_correct_and_a_broken_timed_path_is_not(
        root, fault, monkeypatch, capsys):
    """The program's tables after the checked chunks meet the cell's own
    limits against ``reference/sgns.py``, with no pair id mismatched;
    each fault fails them."""
    code, line = run_cell(root, "tiny.graph", capsys)
    assert code == 0 and line["correct"] is True
    assert line["checks"]["pair_id_mismatches"]["value"] == 0.0
    assert set(line["metrics"]) == {"train_pairs_per_s", "setup_s"}
    fault(monkeypatch)
    code, line = run_cell(root, "tiny.graph", capsys)
    assert code == 0 and line["correct"] is False


# ------------------------------------------------------------ the readers
RUN = SimpleNamespace(config={"negatives": 5},
                      devices=[SimpleNamespace(device_kind="TPU v5 lite")])
COUNTERS = {"sgns.pairs": 2000, "sgns.hot_touches": 7000,
            "graph.walk_ns": 2_000_000_000, "graph.pairs": 100_000_000}


def _outcome(**counters):
    return Outcome(end_to_end={}, setup_s=0.0, attempted=1, failed=0,
                   checks=[], memory_peak_bytes=None, trace=None,
                   counters=dict({"pairs": 50_000_000, "window_s": 10.0},
                                 **counters))


def _read(metric, outcome):
    return Spec().reader(metric).read(outcome, RUN)


@pytest.fixture
def counters(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "read", lambda name: COUNTERS.get(name, 0))


def test_hot_row_share_by_hand(counters):
    # 7000 hot references of 2000 pairs × (5 + 2)
    assert _read("hot_row_share", _outcome()) == pytest.approx(50.0)


def test_walk_host_share_by_hand(counters):
    # 20 ns a pair cut × 5e7 pairs trained in 10 s: a tenth of the window
    assert _read("walk_host_share", _outcome()) == pytest.approx(10.0)
    assert _read("walk_host_share",
                 _outcome(window_s=5.0)) == pytest.approx(20.0)


@pytest.mark.parametrize("metric,missing", [
    ("hot_row_share", "counters"),
    ("hot_row_share", "name"),
    ("hot_row_share", "obs"),
    ("walk_host_share", "counters"),
    ("walk_host_share", "name"),
    ("walk_host_share", "obs"),
    ("walk_host_share", "window"),
])
def test_graph_readers_read_nothing_without_their_counters(
        metric, missing, counters, monkeypatch):
    """No counts, a program that does not name the counter (the parent),
    no ``repro.obs``, or no window: None, never an error."""
    from repro import obs

    out = _outcome()
    assert _read(metric, out) is not None
    if missing == "counters":
        monkeypatch.setattr(obs, "read", lambda name: 0)
    elif missing == "name":
        for name in ("HOT_TOUCHES", "WALK_NS", "GRAPH_PAIRS"):
            monkeypatch.delattr(obs, name)
    elif missing == "obs":
        import repro

        monkeypatch.delattr(repro, "obs")
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    else:
        out.counters.pop("window_s")
    assert _read(metric, out) is None
