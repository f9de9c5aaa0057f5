"""The benchmark's counting functions and peaks, against brute force."""

import itertools

import numpy as np
import pytest

from benchmarks.chip import counting
from benchmarks.chip.device import peaks_for


def test_expected_distinct_matches_enumeration():
    q = np.array([0.5, 0.3, 0.2])
    for draws in (1, 2, 3, 4):
        exact = 0.0
        for seq in itertools.product(range(3), repeat=draws):
            exact += np.prod(q[list(seq)]) * len(set(seq))
        assert counting.expected_distinct(q, draws) == pytest.approx(exact,
                                                                     rel=1e-12)


def test_expected_distinct_excludes_ids_already_seen():
    q = np.array([0.5, 0.3, 0.2])
    seen = np.array([True, False, False])
    exact = 0.0
    for seq in itertools.product(range(3), repeat=3):
        exact += np.prod(q[list(seq)]) * len(set(seq) - {0})
    assert counting.expected_distinct(q, 3, exclude=seen) == \
        pytest.approx(exact, rel=1e-12)


def test_chunk_rows_against_a_brute_force_count():
    rng = np.random.default_rng(0)
    q = rng.random(40)
    q /= q.sum()
    centers = rng.integers(0, 40, size=(3, 8))
    contexts = rng.integers(0, 40, size=(3, 8))
    K, trials = 2, 4000
    w_rows, c_rows = counting.sgns_chunk_rows(centers, contexts, q, K)
    assert w_rows == len(set(centers.ravel().tolist()))
    sims = [len(set(contexts.ravel().tolist())
                | set(rng.choice(40, size=K * contexts.size, p=q).tolist()))
            for _ in range(trials)]
    assert c_rows == pytest.approx(np.mean(sims), abs=4 * np.std(sims)
                                   / np.sqrt(trials))


def test_chunk_floor_never_exceeds_the_per_step_count():
    rng = np.random.default_rng(1)
    for _ in range(20):
        V = int(rng.integers(5, 60))
        q = rng.random(V) ** 3
        q /= q.sum()
        S, B, K, d = 4, int(rng.integers(1, 12)), 3, 7
        centers = rng.integers(0, V, size=(S, B))
        contexts = rng.integers(0, V, size=(S, B))
        chunk = counting.sgns_chunk_bytes(centers, contexts, q, K, d)
        per_step = sum(counting.sgns_chunk_bytes(centers[s:s + 1],
                                                 contexts[s:s + 1], q, K, d)
                       for s in range(S))
        assert chunk <= per_step * (1 + 1e-12)
        assert chunk == 2 * 4 * d * sum(counting.sgns_chunk_rows(
            centers, contexts, q, K))


def test_sgns_flops_per_pair_counts_every_product():
    d, K = 5, 3
    ops = 0
    ops += (K + 1) * 2 * d            # w·c and w·n_k
    ops += 2 * d * (K + 1)            # dW: g_pos·c + Σ g_k·n_k
    ops += 2 * d                      # dC_pos: g_pos·w
    ops += 2 * d * K                  # dC_neg: g_k·w
    assert counting.sgns_flops_per_pair(d, K) == ops


def test_min_seconds_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counting.min_seconds(1000.0, 20.0, peaks) == (10.0, "flops")
    assert counting.min_seconds(100.0, 20.0, peaks) == (2.0, "hbm_bytes")


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 1.97e14 and v5e["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        peaks_for("cpu")
