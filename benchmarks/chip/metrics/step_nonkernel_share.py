"""Device time of the training epoch outside the SGNS kernel's events
(the planner's sorts and dedups, layout pack and unpack, the noise
draw replay), over device busy time. Source: device trace."""

from benchmarks.chip.metrics.kernels import is_sgns_kernel


def read(outcome, run):
    tr = outcome.trace
    if tr is None:
        return None
    busy = tr.busy_ns()
    kernel = tr.op_time(is_sgns_kernel)
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * (busy - kernel) / busy
