"""BENCHMARK.json keeps the benchmark's contract, cells are found by
name, and the command refuses to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench_chip_tiny import REPO, run_cell, tiny_copy
from benchmarks.chip.spec import NAME, UNIT, Spec

SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return Spec()


def test_top_level_keys_and_command(spec):
    d = spec.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(d["paths"]) <= 16 and len(d["command"]) <= 32
    for p in d["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (REPO / p).is_dir()
    assert isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51
    # a full check with 24 cells fits its time
    assert (2 + 14 * 24) * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(d)) <= 64 * 1024


def test_names_units_and_one_line_fields(spec):
    d = spec.data
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    for group in ("configs", "workloads"):
        names += [e["name"] for e in d[group]]
    names += [w["traffic"] for w in d["workloads"]]
    names += [k for c in d["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in d["end_to_end"] + d["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for text in ([c["why"] for c in d["configs"]] + [c["source"] for c in d["configs"]]
                 + [w["why"] for w in d["workloads"]]
                 + [m["layer"] for m in d["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for group in ("end_to_end", "per_layer", "configs", "workloads"):
        assert len({e["name"] for e in d[group]}) == len(d[group])


def test_every_entry_has_exactly_its_keys(spec):
    d = spec.data
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert sum(w["chips"] == 4 for w in d["workloads"]) <= max(
        1, len(d["workloads"]) // 2)


def test_files_are_under_paths_and_found_by_name(spec):
    d = spec.data
    used = {w["config"] for w in d["workloads"]}
    assert used == {c["name"] for c in d["configs"]}
    files = [c["file"] for c in d["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in d["paths"])
        assert (REPO / f).is_file()
    for w in d["workloads"]:
        traffic = spec.traffic(w)
        assert spec.kind(traffic).run
    for m in d["per_layer"]:
        assert callable(spec.reader(m["name"]).read)


def test_every_cell_reports_what_its_metrics_move(spec):
    d = spec.data
    assert "setup_s" in {m["name"] for m in d["end_to_end"]}
    for w in d["workloads"]:
        e2e = {m["name"] for m in spec.end_to_end(w)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.per_layer(w)
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    for m in d["per_layer"]:
        for cell in m.get("workloads", []):
            assert m["moves"] in {e["name"] for e in
                                  spec.end_to_end(spec.cell(cell))}


def test_a_cell_added_as_files_runs_by_name(tmp_path, capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    root = tiny_copy(tmp_path)
    code, line = run_cell(root, "tiny.train", capsys)
    assert code == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"train_pairs_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"]["pair_id_mismatches"]["value"] == 0.0
    assert line["device"]["count"] == 1 and line["attempted"] >= 1


def _command(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.run", "--workload",
         "train.wiki.n4", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_command_prints_no_result():
    proc = _command(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_the_benchmark_alone_prints_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in Spec().data["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
