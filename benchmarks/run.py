"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV summary lines plus the full tables,
and writes per-figure CSVs under benchmarks/out/.

  PYTHONPATH=src python -m benchmarks.run            # all LSH figures
  PYTHONPATH=src python -m benchmarks.run --fast     # skip slow subprocess
  PYTHONPATH=src python -m benchmarks.run --only fig08_query_opt
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: query + build
                                                     # throughput, writes
                                                     # BENCH_query.json and
                                                     # BENCH_build.json
"""

from __future__ import annotations

import argparse
import sys
import time


def _figures(fast: bool):
    from benchmarks import build_throughput as B
    from benchmarks import lsh_figures as F
    from benchmarks import query_throughput as Q
    figs = [
        Q.query_throughput,
        B.build_throughput,
        F.fig02_breakpoints,
        F.fig06_beta_L,
        F.fig07_index_breakdown,
        F.fig08_query_opt,
        F.fig13_vary_L,
        F.fig14_vary_K,
        F.fig16_17_indexing,
        F.fig18_19_quality,
        F.fig20_scalability,
        F.fig21_vary_k,
        F.fig22_23_cumulative,
    ]
    if not fast:
        from benchmarks import parallel_scaling as P
        figs.append(P.fig09_10_12_scaling)
    return figs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip multi-process scaling benchmarks")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: query/build throughput, snapshot "
                         "round-trip, PDET worker scaling, the serving-"
                         "runtime mixed-load check, LSH-decode vs full "
                         "attention, the recall/QPS Pareto sweep on "
                         "small indexes, the auto-tuner shrink-L check, "
                         "and the WAL ingest/recovery check; "
                         "writes BENCH_{query,build,snapshot,parallel,"
                         "serving,decode,pareto,tune,recovery}.json and the "
                         "benchmarks/out/smoke_snapshot artifact")
    ap.add_argument("--only", default="")
    ap.add_argument("--out-dir", default="benchmarks/out")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()

    if args.smoke:
        from benchmarks import build_throughput as B
        from benchmarks import decode_throughput as D
        from benchmarks import parallel_scaling as P
        from benchmarks import pareto_smoke as PS
        from benchmarks import query_throughput as Q
        from benchmarks import recovery_smoke as R
        from benchmarks import serving_load as V
        from benchmarks import snapshot_smoke as S
        from benchmarks import tune_smoke as T
        figures = [Q.query_throughput_smoke, B.build_throughput_smoke,
                   S.snapshot_smoke, P.parallel_scaling_smoke,
                   V.serving_load, D.decode_throughput_smoke,
                   PS.pareto_smoke, T.tune_smoke, R.recovery_smoke]
    else:
        figures = _figures(args.fast)

    summary = ["name,us_per_call,derived"]
    failed, ran = [], []
    for fig in figures:
        if args.only and fig.__name__ != args.only:
            continue
        t0 = time.perf_counter()
        try:
            table = fig()
            ran.append(fig.__name__)
        except Exception as e:  # keep the harness running
            print(f"[bench] {fig.__name__} FAILED: {e}", file=sys.stderr)
            summary.append(f"{fig.__name__},nan,error")
            failed.append(fig.__name__)
            continue
        sec = time.perf_counter() - t0
        lines = table.emit(args.out_dir)
        print(f"\n### {table.name}  ({sec:.1f}s)")
        for ln in lines:
            print(ln)
        us = sec * 1e6 / max(len(table.rows), 1)
        summary.append(f"{table.name},{us:.1f},rows={len(table.rows)}")

    print("\n### summary")
    for ln in summary:
        print(ln)

    if failed:
        raise SystemExit(f"[bench] figures failed: {failed}")
    if args.smoke:
        _enforce_smoke_gates(ran)


def _enforce_smoke_gates(ran) -> None:
    """--smoke is the CI entry point: a build-pipeline regression must
    fail the run, not just print (a failed figure already has).  Gates are
    *ratios* measured within the same run (old-vs-new build speedup >= 1.0),
    not absolute times, so shared CI runners don't flake.  The build gate
    only fires when this run actually produced BENCH_build.json (--only may
    have selected a different figure — never gate on a stale file)."""
    import json
    if "serving_load" in ran:
        with open("BENCH_serving.json") as f:
            srv = json.load(f)
        if not srv["identical_to_oracle"]:
            raise SystemExit("[bench] serving gate: answers diverged from "
                             "the serialized oracle")
        if srv["stats"]["shed_total"] != 0:
            raise SystemExit(f"[bench] serving gate: shed at smoke load: "
                             f"{srv['stats']['shed']}")
        print(f"[bench] serving gates OK: oracle-identical, zero shed, "
              f"p99={srv['stats']['p99_ms']:.1f}ms "
              f"({srv['closed_loop_qps']:.0f} qps closed-loop)")
    if "decode_throughput_smoke" in ran:
        with open("BENCH_decode.json") as f:
            dec = json.load(f)
        if not dec["ratio_lsh_over_full"] >= 1.0:
            raise SystemExit(f"[bench] decode gate: LSH decode slower than "
                             f"full attention at S={dec['S']}: "
                             f"{dec['ratio_lsh_over_full']:.2f}x")
        if not dec["planted_recall"] >= 0.9:
            raise SystemExit(f"[bench] decode gate: planted recall "
                             f"{dec['planted_recall']:.2f} < 0.9 — speed "
                             f"via retrieval misses is not acceptable")
        print(f"[bench] decode gates OK: "
              f"{dec['ratio_lsh_over_full']:.2f}x over full attention, "
              f"planted recall {dec['planted_recall']:.2f} "
              f"(S={dec['S']}, refresh_every={dec['refresh_every']})")
    if "pareto_smoke" in ran:
        with open("BENCH_pareto.json") as f:
            gate = json.load(f)["det_dominates_brute"]
        if not gate["ok"]:
            raise SystemExit(f"[bench] pareto gate: no DET-LSH point beats "
                             f"brute force at recall >= "
                             f"{gate['min_recall']}: {gate}")
        print(f"[bench] pareto gate OK: {gate['best_label']} reaches "
              f"recall {gate['best_recall']:.3f} at "
              f"{gate['best_work']:.0f} candidates/query vs "
              f"{gate['reference_work']:.0f} exact")
    if "tune_smoke" in ran:
        with open("BENCH_tune.json") as f:
            tg = json.load(f)["gates"]
        if not tg["tuner_hit_target"]:
            raise SystemExit(
                f"[bench] tune gate: tuner missed target recall "
                f"{tg['target_recall']}: tuned recall "
                f"{tg['tuned_recall']:.3f} "
                f"(L={tg['tuned_L']}, probe_depth={tg['tuned_probe_depth']})")
        if not tg["shrinks_L_at_fixed_recall"]:
            raise SystemExit(
                f"[bench] tune gate: tuned config does not shrink L at "
                f"fixed recall: L {tg['tuned_L']} vs {tg['baseline_L']}, "
                f"work {tg['tuned_work']:.0f} vs {tg['baseline_work']:.0f}, "
                f"recall {tg['tuned_recall']:.3f} vs target "
                f"{tg['target_recall']}")
        print(f"[bench] tune gates OK: L={tg['tuned_L']} "
              f"p={tg['tuned_probe_depth']} reaches recall "
              f"{tg['tuned_recall']:.3f} at {tg['tuned_work']:.0f} "
              f"candidates/query vs static L={tg['baseline_L']} at "
              f"{tg['baseline_work']:.0f}")
    if "recovery_smoke" in ran:
        with open("BENCH_recovery.json") as f:
            rec = json.load(f)
        if not rec["identical"]:
            raise SystemExit("[bench] recovery gate: recovered index not "
                             "bit-identical to the pre-crash one")
        if not rec["ingest_ratio"] >= 0.5:
            raise SystemExit(f"[bench] recovery gate: WAL-on ingest "
                             f"{rec['ingest_ratio']:.2f}x of WAL-off "
                             f"(< 0.5x parity floor)")
        print(f"[bench] recovery gates OK: bit-identical after replaying "
              f"{rec['replayed']} records in {rec['recovery_s'] * 1e3:.0f}ms,"
              f" WAL ingest parity {rec['ingest_ratio']:.2f}x")
    if "build_throughput_smoke" not in ran:
        print("[bench] build speedup gate skipped (build figure not run)")
        return
    with open("BENCH_build.json") as f:
        speedup = json.load(f)["build_speedup"]
    bad = {k: v for k, v in speedup.items() if not v >= 1.0}
    if bad:
        raise SystemExit(f"[bench] build-pipeline speedup gate (>= 1.0x "
                         f"over the seed builder) failed: {bad}")
    print(f"[bench] build speedup gate OK: "
          + ", ".join(f"{k}={v:.2f}x" for k, v in speedup.items()))


if __name__ == "__main__":
    main()
