"""Run one benchmark cell on the chip and print its result line.

    python3 -m benchmarks.chip.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the repository's ``src/`` is
missing. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``--trace 1`` adds ``busy_s``, ``window_s`` and ``breakdown``) and,
last, ``checks``: each number compared with the plain reference beside
its limit. The same numbers end standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

from benchmarks.chip.device import (NoAccelerator, device_facts,  # noqa: E402
                                    require_chips)
from benchmarks.chip.harness import Run  # noqa: E402
from benchmarks.chip.spec import ROOT, Spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def result_line(spec: Spec, run: Run, out, facts: dict) -> dict:
    units = {m["name"]: m["unit"]
             for m in spec.data["end_to_end"] + spec.data["per_layer"]}
    device = dict(facts, memory_peak_bytes=out.memory_peak_bytes)
    if not run.trace:
        values = dict(out.end_to_end, setup_s=out.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end(run.cell)}
    else:
        metrics = {}
        for m in spec.per_layer(run.cell):
            value = spec.reader(m["name"]).read(out, run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if run.trace and out.trace is not None:
        device["busy_s"] = out.trace.busy_ns() / 1e9
        device["window_s"] = out.trace.window_ns / 1e9
        line["breakdown"] = {"device_ops": out.trace.top_ops(),
                             "idle_gaps": out.trace.top_gaps()}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
         t_start: float | None = None) -> int:
    args = parse(argv)
    spec = Spec(root)
    cell = spec.cell(args.workload)
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"the system under test is missing: no {src}/repro",
              file=sys.stderr)
        return 1
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    try:
        devices = jax.devices()
        devices = (require_chips(devices, cell["chips"]) if require_tpu
                   else devices[:cell["chips"]])
    except NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    traffic = spec.traffic(cell)
    run = Run(cell=cell, config=spec.config(cell), traffic=traffic,
              seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              devices=devices, t_start=T_START if t_start is None else t_start)
    out = spec.kind(traffic).run(run)
    line = result_line(spec, run, out, device_facts(devices))
    print(run.host_report(), file=sys.stderr)
    for c in out.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    raise SystemExit(code)
