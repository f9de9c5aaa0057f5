"""The SGNS kernel's share of its roofline: the least time the chip
could take for the traced chunks, over the kernel events' device time.
The least time is the larger of the algorithm's bytes over the HBM
peak (one read and one write of each distinct row per chunk, d float32
wide, negatives' distinct rows in expectation) and its operations
(6·(K+1)·d per pair) over the FLOP peak (``counting.py``). Source:
device trace."""

from benchmarks.chip import counting
from benchmarks.chip.device import peaks_for
from benchmarks.chip.metrics.kernels import is_sgns_kernel


def read(outcome, run):
    tr, c = outcome.trace, outcome.counters
    if tr is None or "bytes" not in c:
        return None
    kernel_s = tr.op_time(is_sgns_kernel) / 1e9      # per chip
    if kernel_s <= 0:
        return None
    chips = c["chips"]
    floor_s, _ = counting.min_seconds(
        c["traced_pairs"] * c["flops_per_pair"] / chips, c["bytes"] / chips,
        peaks_for(run.devices[0].device_kind))
    return 100.0 * floor_s / kernel_s
