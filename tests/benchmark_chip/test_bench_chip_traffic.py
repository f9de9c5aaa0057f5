"""The benchmark's traffic: seeded generators, and traffic files that
are data."""

import json

import numpy as np

from benchmarks.chip.spec import Spec
from benchmarks.chip.traffic.corpus import semantic_corpus


def test_corpus_same_seed_same_tokens():
    a = semantic_corpus(2 ** 31 + 5, 500, 200)
    b = semantic_corpus(2 ** 31 + 5, 500, 200)
    c = semantic_corpus(6, 500, 200)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0][:100], c[0][:100])
    tokens, offsets = a
    assert offsets[-1] == len(tokens) and len(offsets) == 201
    assert tokens.min() >= 0 and tokens.max() < 500


def test_traffic_files_are_data():
    spec = Spec()
    for path in sorted((spec.dir / "traffic").glob("*.json")):
        traffic = json.loads(path.read_text())
        assert (spec.dir / "kinds" / f"{traffic['kind']}.py").is_file()
        assert set(traffic["limits"]) and all(
            isinstance(v, (int, float)) for v in traffic["limits"].values())
