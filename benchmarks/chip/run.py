#!/usr/bin/env python3
"""Chip benchmark of the seizure-prediction system: one run of one cell.

  python3 benchmarks/chip/run.py --workload serve.reconnect --seed 7 \
      --seconds 30 --trace 0

Run from the root of a checkout. ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window. The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, then
``checks``: every number compared with the reference beside its limit,
repeated as the last lines of stderr). The run refuses to start, and
prints no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def fail(msg: str) -> None:
    print(f"[bench] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program is not in this checkout ({ROOT / 'src'} missing)")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))

    from chipbench import cell, peaks

    spec = cell.load_spec(ROOT, args.workload)
    chips = spec[1]["chips"]

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    visible = jax.devices()
    if visible[0].platform != "tpu":
        fail(f"JAX finds no TPU (platform {visible[0].platform!r})")
    if len(visible) < chips:
        fail(f"{chips} chips asked for, {len(visible)} visible")
    devices = visible[:chips]
    peaks.lookup(devices[0].device_kind)

    result = cell.run(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices, T_START, spec=spec)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
