"""The whole training step's share of the chip's FLOP peak: 6·(K+1)·d
operations per pair times the traced run's pairs per second, over
chips × peak. Source: host clock."""

from benchmarks.chip.device import peaks_for


def read(outcome, run):
    c = outcome.counters
    if not c.get("pairs") or c.get("window_s", 0) <= 0:
        return None
    peak = peaks_for(run.devices[0].device_kind)["flops_per_s"]
    rate = c["pairs"] * c["flops_per_pair"] / c["window_s"]
    return 100.0 * rate / (c["chips"] * peak)
