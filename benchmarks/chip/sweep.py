#!/usr/bin/env python3
"""Rate sweep of an open-loop serving mix on one chip, to find its knee.

  python3 benchmarks/chip/sweep.py --config freiburg3-serve --traffic steady \
      --rates 40 80 120 --seconds 20 --seed 5

For each offered rate (chunks per second) the mix's fleet is opened on a
fresh engine and driven for ``--seconds``; the pool and the served
program are made once. Prints one JSON object per rate: chunks scored per
second, latency percentiles from the due time, unscored chunks at the
window's middle and end (a backlog that grows means the rate is above
what the engine sustains), and how late the generator pushed. The knee is
the highest rate whose p99 stays within ``--p99-limit-ms`` with no
growing backlog. Benchmark runs never run this file.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--p99-limit-ms", type=float, default=2000.0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))

    import jax
    import numpy as np

    from chipbench import cell, serve, spans

    cell.use_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: JAX finds no TPU")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cfg = json.loads((ROOT / files[args.config]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{args.traffic}.json").read_text())
    prep = serve.prepare(cfg, traffic, args.seed, cell.log)
    knee = None
    for rate in args.rates:
        mix = dict(traffic, rate=rate)
        fleet = serve.open_fleet(prep, cfg, mix, jax.devices()[:1], cell.log)
        w = serve.run_window(fleet, args.seconds, args.seed,
                             spans.Recorder(False))
        lat = np.asarray(w.latencies_s) * 1e3
        late = np.asarray(w.lateness_s) * 1e3
        half = w.backlog[len(w.backlog) // 2] if w.backlog else 0
        end = w.backlog[-1] if w.backlog else 0
        p99 = float(np.percentile(lat, 99)) if lat.size else float("inf")
        row = {
            "rate": rate, "sessions": len(fleet.sessions),
            "chunks_per_s": w.scored_in_window / w.seconds,
            "p50_chunk_ms": float(np.percentile(lat, 50)) if lat.size else None,
            "p99_chunk_ms": p99, "chunks": int(lat.size),
            "backlog_mid": int(half), "backlog_end": int(end),
            "generator_late_p99_ms": float(np.percentile(late, 99))
            if late.size else None,
            "steps": w.steps, "window_s": w.seconds,
        }
        growing = end > max(2 * half, half + cfg["engine"]["max_batch"])
        if p99 <= args.p99_limit_ms and not growing:
            knee = rate
        print(json.dumps(row), flush=True)
        del fleet, w
    print(json.dumps({"knee_rate": knee, "p99_limit_ms": args.p99_limit_ms}))


if __name__ == "__main__":
    main()
