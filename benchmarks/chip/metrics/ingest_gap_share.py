"""Share of the traced window in which the device sat idle while the
host waited on the prefetched chunk stream (the harness's
``ingest.wait`` span around ``next()``): idle time the ingestion layer
(``data/pipeline.py``) leaves exposed. Source: device trace."""


def read(outcome, run):
    tr = outcome.trace
    if tr is None or tr.window_ns <= 0:
        return None
    return 100.0 * tr.idle_in_span("ingest.wait") / tr.window_ns
