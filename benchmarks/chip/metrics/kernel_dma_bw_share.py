"""The share of the HBM peak the SGNS kernel reaches on the bytes it
actually moves: the program's ``sgns.bytes_moved`` per ``sgns.pairs``
(both counters summed over the run, checked chunks included; the stream
is stationary, so the ratio stands for the window) times the traced
pairs, per chip, over the kernel events' device time and the peak of
``peaks.json``. Source: device trace."""

from benchmarks.chip.device import peaks_for
from benchmarks.chip.metrics.kernels import is_sgns_kernel


def read(outcome, run):
    try:
        from repro import obs
    except ImportError:
        return None
    tr, c = outcome.trace, outcome.counters
    if tr is None or not c.get("traced_pairs"):
        return None
    pairs, moved = obs.read(obs.PAIRS), obs.read(obs.BYTES_MOVED)
    kernel_s = tr.op_time(is_sgns_kernel) / 1e9      # per chip
    if pairs <= 0 or moved <= 0 or kernel_s <= 0:
        return None
    bytes_per_chip = moved / pairs * c["traced_pairs"] / c["chips"]
    peak = peaks_for(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * bytes_per_chip / kernel_s / peak
