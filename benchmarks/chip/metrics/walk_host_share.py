"""Share of the window the graph walk source would need on one host
thread to produce what the window trained: the program's host
nanoseconds in its ``repro.ingest.walk`` spans per pair cut
(``graph.walk_ns`` / ``graph.pairs``, summed over the run) times the
window's trained pairs, over the window's seconds. 100% means the walk
source sets the pace. None where the program has no walk source.
Source: host clock."""


def read(outcome, run):
    try:
        from repro import obs
    except ImportError:
        return None
    c = outcome.counters
    ns_name = getattr(obs, "WALK_NS", None)
    pairs_name = getattr(obs, "GRAPH_PAIRS", None)
    if ns_name is None or pairs_name is None:
        return None
    cut, ns = obs.read(pairs_name), obs.read(ns_name)
    if cut <= 0 or ns <= 0 or not c.get("pairs") or c.get("window_s", 0) <= 0:
        return None
    return 100.0 * ns / cut * c["pairs"] / (c["window_s"] * 1e9)
