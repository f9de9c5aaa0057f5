"""Benchmark harness — one benchmark per paper table/figure.

  bench_kl        — Fig 1   (KL divergence of sampling strategies)
  bench_sampling  — Table 2 (equal/random/shuffle × rates vs sync baseline)
  bench_merge     — Table 3 (Concat/PCA/ALiR/average/single)
  bench_wallclock — Table 4 + Fig 2 (training/merge wall-clock, scaling)
  bench_oov       — Fig 3   (missing-vocabulary reconstruction)
  bench_kernel    — SGNS step micro-bench + Pallas/oracle check +
                    update-engine sweep (dense/sparse/pallas/
                    pallas_fused_tiered, incl. the fused engine's
                    bit-equivalence and its hot-fraction ladder)
  bench_serve     — serving tier (p50/p99 lookup latency, coalesced
                    batch size, cache hit rate under concurrent clients)
  roofline_table  — §Roofline terms from the dry-run sweeps

Prints a final ``name,us_per_call,derived`` CSV summary.
Env: REPRO_BENCH_QUICK=1 for reduced step counts;
     REPRO_BENCH_ONLY=a,b to run only the named benchmarks.
Args: --out FILE writes the CSV summary to FILE (CI artifact).
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the CSV summary to this file")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    quick = os.environ.get("REPRO_BENCH_QUICK", "0") == "1"
    only = os.environ.get("REPRO_BENCH_ONLY", "")
    only_names = {n.strip() for n in only.split(",") if n.strip()}
    csv: list[tuple[str, float, str]] = []
    seen_names: set[str] = set()

    def run(name, fn, derive):
        seen_names.add(name)
        if only_names and name not in only_names:
            return
        t0 = time.perf_counter()
        try:
            out = fn(quick=quick)
            us = (time.perf_counter() - t0) * 1e6
            csv.append((name, us, derive(out)))
        except Exception as e:  # keep the harness running
            csv.append((name, -1.0, f"FAILED:{type(e).__name__}"))
            import traceback
            traceback.print_exc()

    from benchmarks import (bench_kl, bench_sampling, bench_merge,
                            bench_wallclock, bench_oov, bench_kernel,
                            bench_serve, roofline_table)

    run("fig1_kl", lambda quick: bench_kl.main(),
        lambda rows: "kl_random<kl_equal=%s" % (
            next(r for r in rows if r['strategy'] == 'random')['kl_unigram'] <
            next(r for r in rows if r['strategy'] == 'equal')['kl_unigram']))
    run("table2_sampling", bench_sampling.main,
        lambda rows: "best=%s" % max(
            (r for r in rows if r['strategy'] != 'sync-baseline'),
            key=lambda r: r['similarity'])['strategy'])
    run("table3_merge", bench_merge.main,
        lambda rows: "best=%s" % max(rows, key=lambda r: r['similarity'])['method'])
    run("table4_wallclock", bench_wallclock.main,
        lambda rows: "speedup=%.1fx;engine_rows=%d" % (
            rows["speedup_projected"], len(rows["engines"])))
    run("fig3_oov", bench_oov.main,
        lambda rows: "alir@50%%sim=%.3f" % next(
            r['similarity'] for r in rows
            if r['method'] == 'alir_pca' and r['removed_frac'] == 0.5))
    run("neg_sampler",
        lambda quick: bench_sampling.negative_sampler_microbench(quick=quick),
        lambda rows: "alias_speedup@V=%d=%.1fx" % (
            rows[-1]["V"], rows[-1]["speedup"]))
    run("kernel_sgns", bench_kernel.main,
        lambda r: "pairs_per_s=%.2e;fused_tiered_err=%.1e;engines=%s;"
                  "hot_sweep=%s" % (
            r["pairs_per_s_sparse"], r["fused_tiered_vs_sparse_err"],
            "|".join("%s:%.0fus" % (n, us)
                     for n, us in r["engine_us"].items()),
            "|".join("%d:%.0fus" % (h["hot_rows"], h["us"])
                     for h in r["tiered_hot_sweep"])))
    run("serve_tier", bench_serve.main,
        lambda r: "p50_ms=%.2f;p99_ms=%.2f;mean_batch=%.1f;hit_rate=%.2f" % (
            r["p50_ms"], r["p99_ms"], r["mean_batch"], r["cache_hit_rate"]))
    run("roofline", roofline_table.main, lambda r: "see tables above")

    lines = [f"{name},{us:.1f},{derived}" for name, us, derived in csv]
    print("\n=== summary (name,us_per_call,derived) ===")
    print("\n".join(lines))
    if args.out:
        with open(args.out, "w") as f:
            f.write("name,us_per_call,derived\n" + "\n".join(lines) + "\n")
    unknown = only_names - seen_names
    if unknown:
        print(f"REPRO_BENCH_ONLY names not found: {sorted(unknown)}; "
              f"known: {sorted(seen_names)}", file=sys.stderr)
        sys.exit(2)
    if any(us < 0 for _, us, _ in csv):
        sys.exit(1)


if __name__ == "__main__":
    main()
