"""Readings that set a cell's limits: the program's, the control's and
the planted faults', each against the plain reference, on many seeds in
one process (set-up is long; the chip is held once).

    python3 -m benchmarks.chip.calibrate --workload train.wiki.n4 \
        --seeds 11 12 13

Prints one JSON line per seed and reading. Not part of a benchmark run.
For a training cell: ``reference_leaves`` (each worker's W and C change
after the first and the last checked chunk, over the median leaf's),
``program`` (the trainer through its first chunks),
``control`` (the reference with bfloat16 tables in the program's place)
and ``half_batch`` (the reference training on half of each batch, the
mean over the rest). A state left unchanged reads 1 by construction,
and an altered id is a pair-id mismatch, so neither needs a run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmarks.chip.device import require_chips
from benchmarks.chip.harness import Run
from benchmarks.chip.spec import ROOT, Spec


def train(run: Run) -> dict:
    from benchmarks.chip.kinds import train as kind

    tokens, offsets = kind.make_corpus(run.traffic, run.seed)
    prog = kind.Program(run, tokens, offsets)
    readings = kind.program_readings(prog, run.traffic["check_chunks"])
    prog.close()
    del prog
    ids = kind.reference_ids(run, tokens, offsets)
    ref = kind.reference_readings(run, ids)
    leaves = {}      # each leaf's reference norm over the median leaf's
    for key in ("first", "last"):
        norms = np.asarray(ref[key])
        for t, table in enumerate(kind.TABLES):
            leaves[f"{key}.{table}"] = (norms[:, t]
                                        / np.median(norms)).tolist()
    return {"reference_leaves": leaves,
            "program": kind.gaps(readings, ref),
            "control": kind.gaps(kind.reference_readings(
                run, ids, dtype="bfloat16"), ref),
            "half_batch": kind.gaps(kind.reference_readings(
                run, ids, keep=0.5), ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = Spec(ROOT)
    cell = spec.cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = require_chips(jax.devices(), cell["chips"])
    enable_compile_cache()
    traffic = spec.traffic(cell)
    readings = {"train": train}[traffic["kind"]]
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = Run(cell=cell, config=spec.config(cell), traffic=traffic,
                  seed=seed, seconds=0.0, trace=False, devices=devices,
                  t_start=t0)
        for name, values in readings(run).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "reading": name, **values}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
