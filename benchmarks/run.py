"""Benchmark harness entrypoint: one bench per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only substr] [--smoke]

CSV rows: ``name,us_per_call_or_value,derived``. ``--smoke`` runs the
smoke-capable benches on tiny shapes with 1 rep and writes a
``BENCH_*.json`` artifact (what CI uploads per PR to record the perf
trajectory).
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on bench module name")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 rep, JSON artifact; only benches "
                         "that support smoke mode run")
    ap.add_argument("--json", default=None,
                    help="write rows to this JSON path "
                         "(default BENCH_smoke.json with --smoke)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_dataset_size, bench_execution_time,
                            bench_kernels, bench_mspca_denoise,
                            bench_prediction_timeline, bench_serving,
                            bench_train_forest, bench_training_accuracy,
                            roofline)
    from benchmarks.common import Rows

    benches = [
        ("bench_training_accuracy", bench_training_accuracy.run),
        ("bench_execution_time", bench_execution_time.run),
        ("bench_prediction_timeline", bench_prediction_timeline.run),
        ("bench_dataset_size", bench_dataset_size.run),
        ("bench_mspca_denoise", bench_mspca_denoise.run),
        ("bench_kernels", bench_kernels.run),
        ("bench_serving", bench_serving.run),
        ("bench_train_forest", bench_train_forest.run),
        ("roofline", roofline.run),
    ]
    rows = Rows()
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        takes_smoke = "smoke" in inspect.signature(fn).parameters
        if args.smoke and not takes_smoke:
            continue
        t0 = time.time()
        try:
            # Count XLA compilations per bench (repro.analysis
            # sanitizers): a jump in a bench's compile count between
            # artifacts flags a recompile regression (shape/weak-type
            # drift) even when the timed rows still look healthy.
            from repro.analysis.sanitizers import CompileCounter

            with CompileCounter() as cc:
                fn(rows, smoke=args.smoke) if takes_smoke else fn(rows)
            rows.add(f"{name}/compiles", float(cc.total),
                     "XLA compilations during the bench")
        except Exception as e:  # keep the harness going; report
            failures += 1
            rows.add(f"{name}/ERROR", 0.0, f"{type(e).__name__}: {e}")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    json_path = args.json or ("BENCH_smoke.json" if args.smoke else None)
    if json_path:
        rows.to_json(json_path, smoke=args.smoke)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
