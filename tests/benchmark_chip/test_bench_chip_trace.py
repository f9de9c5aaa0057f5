"""The trace reduction on a small committed trace, against values worked
out by hand from ``small_trace.pbtxt``.

Device 0, window [0, 10000) ns: ops [1000, 3000) and [2500, 4000)
overlap, the kernel runs [6000, 8000), and the last op is cut at the
window's end to [9500, 10000). Busy = 3000 + 2000 + 500 = 5500 ns;
idle gaps [0, 1000), [4000, 6000), [8000, 9500). Host spans: dispatch
[0, 500), ingest.wait [3500, 6500), sync [7500, 9800). Device 1 is
busy throughout.
"""

from pathlib import Path

import pytest

from benchmarks.chip import trace
from benchmarks.chip.metrics.kernels import is_sgns_kernel

TRACE = Path(__file__).with_name("small_trace.pbtxt")


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(TRACE.read_text())


def test_busy_and_idle_share_on_one_device(profile):
    tr = trace.reduce(profile, devices={0})
    assert tr.window == (0.0, 10000.0)
    assert tr.busy(0) == [(1000.0, 4000.0), (6000.0, 8000.0), (9500.0, 10000.0)]
    assert tr.busy_ns() == 5500.0
    assert tr.idle_share() == pytest.approx(0.45)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in(profile):
    tr = trace.reduce(profile, devices={0})
    assert tr.idle_in_span("ingest.wait") == 2000.0
    assert tr.idle_in_span("sync") == 1500.0
    assert tr.idle_in_span("dispatch") == 500.0
    assert tr.top_gaps() == [["ingest.wait", 2e-6], ["sync", 1.5e-6],
                             ["dispatch", 1e-6]]


def test_kernel_events_and_top_ops(profile):
    tr = trace.reduce(profile, devices={0})
    assert tr.op_time(is_sgns_kernel) == 2000.0
    assert tr.op_time(lambda n: not is_sgns_kernel(n)) == 2000.0 + 1500.0 + 500.0
    assert tr.top_ops()[0] == ["fusion.1", 2.5e-6]


def test_busy_time_is_averaged_over_devices(profile):
    tr = trace.reduce(profile)
    assert sorted(tr.ops) == [0, 1]
    assert tr.busy_ns() == (5500.0 + 10000.0) / 2
    assert tr.idle_share() == pytest.approx(0.225)


def test_interval_helpers():
    assert trace.merge_intervals([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.overlap([(0, 3), (5, 9)], [(2, 6)]) == 2.0


def test_a_trace_without_device_ops_is_refused():
    from jax.profiler import ProfileData

    host_only = TRACE.read_text().split("planes {\n  id: 3")[0].replace(
        "TPU", "XXX1")
    with pytest.raises(ValueError):
        trace.reduce(ProfileData.from_text_proto(host_only))
