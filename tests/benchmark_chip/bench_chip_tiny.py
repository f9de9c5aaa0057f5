"""A copy of the benchmark with tiny cells added, for CPU tests: the
harness, the references and the program run end to end at sizes a
test can hold (the kernels in interpret mode)."""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_TRAIN = {
    "corpus": {"generator": "semantic_corpus", "vocab_size": 2000,
               "sentences": 3000},
    "workers": 2, "rate": 0.5, "batch": 64,
    "steps_per_chunk": 8, "sentences_per_block": 64,
    "lr_total_steps": 1000,
}


def tiny_copy(tmp: Path) -> Path:
    """``tmp`` holding BENCHMARK.json, the harness and the program, with
    the cell ``tiny.train`` added as files and entries: a copy of the
    wiki cell at d = 16 and tables of 2048 rows (more than the union
    vocabulary of its 2000 raw words), held to the wiki cell's limits."""
    shutil.copy(REPO / "BENCHMARK.json", tmp)
    shutil.copytree(REPO / "benchmarks" / "chip", tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", tmp / "src")
    here = tmp / "benchmarks" / "chip"
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    cfg = json.loads((here / "configs" / "sgns_wiki.json").read_text())
    cfg["dim"] = 16
    cfg["max_vocab"] = 2048
    (here / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "tiny", "source": "a test",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": ["dim", "max_vocab"], "why": "a test"})
    traffic = json.loads((here / "traffic" / "train.wiki.n4.json").read_text())
    traffic.update(TINY_TRAIN)
    (here / "traffic" / "tiny.train.json").write_text(json.dumps(traffic))
    bench["workloads"].append({"name": "tiny.train", "config": "tiny",
                               "traffic": "tiny.train", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "train.wiki.n4" in m.get("workloads", []):
            m["workloads"].append("tiny.train")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_cell(root: Path, cell: str, capsys, seed: int = 2 ** 31 + 77,
             seconds: float = 1.0) -> tuple[int, dict | None]:
    """Run ``cell`` through the harness on the CPU; the exit code and the
    parsed result line (None when none was printed)."""
    import time

    from benchmarks.chip import run

    capsys.readouterr()
    code = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", "0"], root=root,
                    require_tpu=False, t_start=time.perf_counter())
    out = capsys.readouterr().out.strip().splitlines()
    return code, (json.loads(out[-1]) if out else None)
