"""1 - (union of device-op intervals) / window over the traced training
window, the mean over the chips used. Source: device trace."""


def read(outcome, run):
    tr = outcome.trace
    if tr is None or tr.window_ns <= 0:
        return None
    return 100.0 * tr.idle_share()
