"""The comparison that decides ``correct`` fails what it must.

The control (the plain reference computed one precision step below
what the configuration states) and each fault the cells can have,
planted in the timed path underneath the harness, must come out not
correct at the cells' own limits, here at a size a test can hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_chip_tiny import run_cell, tiny_copy
from benchmarks.chip.harness import Run
from benchmarks.chip.kinds import train as train_kind
from benchmarks.chip.spec import Spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(root, cell, seed=2 ** 31 + 3):
    spec = Spec(root)
    c = spec.cell(cell)
    return Run(cell=c, config=spec.config(c), traffic=spec.traffic(c),
               seed=seed, seconds=1.0, trace=False,
               devices=jax.devices()[:1], t_start=0.0)


def _failed(values, limits):
    return [k for k, lim in limits.items() if not values[k] <= lim]


def test_train_control_bfloat16_tables_is_not_correct(root):
    run = _run(root, "tiny.train")
    tokens, offsets = train_kind.make_corpus(run.traffic, run.seed)
    ids = train_kind.reference_ids(run, tokens, offsets)
    ref = train_kind.reference_readings(run, ids)
    ctl = train_kind.reference_readings(run, ids, dtype="bfloat16")
    assert _failed(train_kind.gaps(ctl, ref), run.traffic["limits"])


def _unchanged_state(monkeypatch):
    from repro.core.async_trainer import AsyncShardTrainer
    epoch = AsyncShardTrainer.epoch

    def broken(self, params, *args, **kw):
        _, losses = epoch(self, jax.tree.map(jnp.copy, params), *args, **kw)
        return params, losses
    monkeypatch.setattr(AsyncShardTrainer, "epoch", broken)


def _half_batch(monkeypatch):
    from repro.core.async_trainer import AsyncShardTrainer
    epoch = AsyncShardTrainer.epoch

    def broken(self, params, centers, contexts, *args, **kw):
        half = centers.shape[-1] // 2
        return epoch(self, params, centers[..., :half], contexts[..., :half],
                     *args, **kw)
    monkeypatch.setattr(AsyncShardTrainer, "epoch", broken)


def _altered_token(monkeypatch):
    from repro.data.pipeline import PairChunkStream
    chunks = PairChunkStream.chunks

    def broken(self, *args, **kw):
        for k, (c, x) in enumerate(chunks(self, *args, **kw)):
            if k == 0:
                c = c.copy()
                at = np.argwhere(c != x)[0]
                c[tuple(at)] = x[tuple(at)]
            yield c, x
    monkeypatch.setattr(PairChunkStream, "chunks", broken)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", _unchanged_state),
    ("tiny.train", _half_batch),
    ("tiny.train", _altered_token),
], ids=lambda v: getattr(v, "__name__", v))
def test_a_broken_timed_path_is_not_correct(root, cell, fault, monkeypatch,
                                             capsys):
    code, line = run_cell(root, cell, capsys)
    assert code == 0 and line["correct"] is True
    fault(monkeypatch)
    code, line = run_cell(root, cell, capsys)
    assert code == 0 and line["correct"] is False
