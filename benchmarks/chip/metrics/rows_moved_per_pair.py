"""Table rows the SGNS kernel moved between HBM and VMEM per trained
pair: the program's ``sgns.rows_moved`` counter over its ``sgns.pairs``
counter, each summed over the whole run. Exact, and the same on every
run of one seed that trains as many chunks. The counters cover the
checked chunks of set-up as well as the window's; the stream is
stationary, so the ratio stands for the window. Source: program
counters."""


def read(outcome, run):
    try:
        from repro import obs
    except ImportError:
        return None
    pairs, rows = obs.read(obs.PAIRS), obs.read(obs.ROWS_MOVED)
    if pairs <= 0 or rows <= 0:
        return None
    return rows / pairs
