"""Share of the SGNS step's row references that the hot tier serves:
the program's ``sgns.hot_touches`` (centres, contexts and negatives
with ``id < hot_rows``, summed on the device per chunk) over
``sgns.pairs · (K + 2)``, the references the trained pairs make. Both
counters cover the whole run, checked chunks included; the stream is
stationary, so the ratio stands for the window. None where the program
does not count hot references. Source: program counters."""


def read(outcome, run):
    try:
        from repro import obs
    except ImportError:
        return None
    name = getattr(obs, "HOT_TOUCHES", None)
    pairs = obs.read(obs.PAIRS)
    if name is None or pairs <= 0:
        return None
    refs = pairs * (run.config["negatives"] + 2)
    return 100.0 * obs.read(name) / refs
