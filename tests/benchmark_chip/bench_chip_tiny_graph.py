"""The tiny copy of the benchmark (``bench_chip_tiny.py``) with a tiny
graph cell added, for CPU tests of the graph cell's kind
(``kinds/train_graph.py``)."""

from __future__ import annotations

import json
from pathlib import Path

from bench_chip_tiny import tiny_copy

TINY_GRAPH = {"dim": 16, "nodes": 3000, "edges": 9000, "max_degree": 300,
              "workers_held": 2}
TINY_GRAPH_TRAFFIC = {"workers": 4, "rate": 0.25, "batch": 64,
                      "steps_per_chunk": 8, "walks_per_block": 64,
                      "lr_total_steps": 1000}


def tiny_graph_copy(tmp: Path) -> Path:
    """``tiny_copy(tmp)`` with the cell ``tiny.graph``: the DeepWalk
    cell at d = 16 on a 3000-node graph, 4 workers over 2 processes
    (this one holding 2), held to the DeepWalk cell's limits and
    reporting what that cell reports."""
    root = tiny_copy(tmp)
    here = root / "benchmarks" / "chip"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((here / "configs" / "deepwalk_youtube.json").read_text())
    cfg.update(TINY_GRAPH)
    (here / "configs" / "tiny_graph.json").write_text(json.dumps(cfg))
    bench["configs"].append({
        "name": "tiny_graph", "source": "a test",
        "file": "benchmarks/chip/configs/tiny_graph.json",
        "reduced": sorted(TINY_GRAPH), "why": "a test"})
    traffic = json.loads(
        (here / "traffic" / "train.deepwalk.n10.json").read_text())
    traffic.update(TINY_GRAPH_TRAFFIC)
    (here / "traffic" / "tiny.graph.json").write_text(json.dumps(traffic))
    bench["workloads"].append({"name": "tiny.graph", "config": "tiny_graph",
                               "traffic": "tiny.graph", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train.deepwalk.n10" in m.get("workloads", []):
            m["workloads"].append("tiny.graph")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
