"""The benchmark's readers on a trace of the program with its own
tracing, and the readers of the program's counters, against values
worked out by hand from ``small_trace_scopes.pbtxt``.

Device 0, window [0, 10000) ns. Ops: the planner's loop op [1000, 4000)
holding a body op [1500, 2500), a planner sort [4000, 5000), the kernel
``sgns_hbm_pipe`` [5000, 7000), a layout pad [7000, 7500), a copy of the
scan [7500, 8000), an op with no scope [9000, 9500): busy 7500 ns, the
kernel 2000. Idle gaps [0, 1000), [8000, 9000), [9500, 10000). The
benchmark's spans: dispatch [0, 1200), ingest.wait [7800, 9200), sync
[9400, 9800). The trace also holds the ops' scope paths and the
program's ``repro.`` spans, on the main thread and on the prefetch
thread; the benchmark's reduction keeps neither, so every number it
gives is the one it gives without them. Device 1 is busy throughout.
"""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.chip import trace
from benchmarks.chip.harness import Outcome
from benchmarks.chip.metrics.kernels import is_sgns_kernel
from benchmarks.chip.spec import Spec

HERE = Path(__file__).parent
SCOPES = HERE / "small_trace_scopes.pbtxt"
RUN = SimpleNamespace(devices=[SimpleNamespace(device_kind="TPU v5 lite")])
# traced_pairs × bytes per pair (16,380,000 / 2000) = 819,000 B in the
# kernel's 2 µs: 4.095e11 B/s, half of the v5e's 8.19e11
COUNTERS = {"sgns.pairs": 2000, "sgns.rows_moved": 26000,
            "sgns.bytes_moved": 16_380_000}


def _trace(path=SCOPES, devices=frozenset({0})):
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(path.read_text())
    return trace.reduce(ProfileData.from_serialized_xspace(data),
                        set(devices))


def _outcome(tr, **counters):
    return Outcome(end_to_end={}, setup_s=0.0, attempted=1, failed=0,
                   checks=[], memory_peak_bytes=None, trace=tr,
                   counters=dict({"chips": 1, "traced_pairs": 100},
                                 **counters))


def _read(metric, outcome):
    return Spec().reader(metric).read(outcome, RUN)


@pytest.fixture
def counters(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "read", lambda name: COUNTERS.get(name, 0))


def test_the_kernel_is_found_under_its_program_name():
    tr = _trace()
    assert [e.name for e in tr.ops[0] if is_sgns_kernel(e.name)] == [
        "sgns_hbm_pipe.4"]
    assert tr.op_time(is_sgns_kernel) == 2000.0
    assert _read("step_nonkernel_share", _outcome(tr)) == pytest.approx(
        100 * 5500 / 7500)


def test_the_programs_spans_leave_the_benchmarks_readings_as_they_were():
    tr = _trace()
    assert set(tr.spans) == {"dispatch", "ingest.wait", "sync"}
    assert tr.busy_ns() == 7500.0
    assert tr.top_gaps() == [["dispatch", 1e-6], ["ingest.wait", 1e-6],
                             ["sync", 5e-7]]
    assert _read("ingest_gap_share", _outcome(tr)) == pytest.approx(10.0)
    assert _read("device_idle_share.train", _outcome(tr)) == pytest.approx(
        25.0)


def test_counter_readers(counters):
    out = _outcome(_trace())
    assert _read("rows_moved_per_pair", out) == 13.0
    assert _read("kernel_dma_bw_share", out) == pytest.approx(50.0)


def test_rows_moved_per_pair_needs_no_trace(counters):
    assert _read("rows_moved_per_pair", _outcome(None)) == 13.0


def test_kernel_dma_bw_share_is_per_chip_and_per_traced_pair(counters):
    tr = _trace()
    assert _read("kernel_dma_bw_share",
                 _outcome(tr, chips=2)) == pytest.approx(25.0)
    assert _read("kernel_dma_bw_share",
                 _outcome(tr, traced_pairs=150)) == pytest.approx(75.0)


def _no_kernel(out):
    tr = out.trace
    out.trace = trace.Trace(
        window=tr.window, spans=tr.spans,
        ops={d: [e for e in evs if not is_sgns_kernel(e.name)]
             for d, evs in tr.ops.items()})


def _no_trace(out):
    out.trace = None


def _untraced(out):
    del out.counters["traced_pairs"]


@pytest.mark.parametrize("metric,missing", [
    ("rows_moved_per_pair", "counters"),
    ("rows_moved_per_pair", "obs"),
    ("kernel_dma_bw_share", "counters"),
    ("kernel_dma_bw_share", "obs"),
    ("kernel_dma_bw_share", "trace"),
    ("kernel_dma_bw_share", "traced_pairs"),
    ("kernel_dma_bw_share", "kernel"),
])
def test_readers_read_nothing_where_their_input_is_missing(
        metric, missing, counters, monkeypatch):
    """No counts, no ``repro.obs`` (the program before it counted), no
    trace, no traced pairs, or no kernel events: None, never an error."""
    out = _outcome(_trace())
    assert _read(metric, out) is not None
    if missing == "counters":
        from repro import obs

        monkeypatch.setattr(obs, "read", lambda name: 0)
    elif missing == "obs":
        import repro

        monkeypatch.delattr(repro, "obs")
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    else:
        {"trace": _no_trace, "traced_pairs": _untraced,
         "kernel": _no_kernel}[missing](out)
    assert _read(metric, out) is None
