#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans, counters and
scopes: one traced window per seed, on the chip.

  python3 benchmarks/chip/split.py --workload serve.reconnect \
      --seeds 7 8 --seconds 30

Runs the cell's set-up and one traced window as ``run.py --trace 1``
does (``cell.measure``), without the comparison, and prints one JSON
object per seed: the cell's per-layer metrics, each read by its own
reader (``metrics/<name>.py``); the sums that show whether the program's
spans and scopes cover their layers (``coverage``); the device time of
every scope; the longest idle gaps named by program spans; and the
compilations in the window.

``--record FILE`` also writes one step (serving) or one fit (training)
of the first seed's trace as a small recorded trace for the CPU tests:
the events that overlap it under a ``bench.window`` of its own, times
from its start, the first chip only, each operation by its short name
with its scope path stored once in ``paths`` (``expand`` puts them
back), and the counters that cover it. Benchmark runs never run this
file.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]

# The marks that together cover a layer: the program's host spans of one
# ``SeizureEngine`` step (beside ``bench.poll``), and the device stages of
# the engine step or of a fit (beside its device time).
COVERAGE = {
    "serve": {"spans": ("fill", "assemble", "put", "dispatch", "readback",
                        "events"),
              "stages": ("mspca", "wpd", "vote", "ring")},
    "train": {"stages": ("featurize", "moments", "rotate", "grow",
                         "gather")},
}


def coverage(red, prog, counters: dict, kind: str) -> dict:
    """Per engine step (serving) or per fit (training): the sum of the
    program's spans of a step, the sum of its device stages, and every
    scope's device time; to set beside ``bench.poll`` and the step's or
    fit's device time."""
    if kind == "serve":
        per = red.program("_engine_step_megabatch")[1]
        host = counters["engine_steps"]
    else:
        per = host = counters["fits"]
    scope = prog.first_scope_s()
    out = {"scope_ms": {n: s * 1e3 / per for n, s in sorted(scope.items())},
           "stages_sum_ms": sum(scope.get(n, 0.0) for n in
                                COVERAGE[kind]["stages"]) * 1e3 / per}
    if "spans" in COVERAGE[kind]:
        out["spans_sum_ms"] = sum(
            prog.span_s.get("seizure." + n, 0.0)
            for n in COVERAGE[kind]["spans"]) * 1e3 / host
    return out


# What a recorded excerpt covers: one engine step (from one
# ``seizure.assemble`` to the next: put, dispatch, read-back, events and
# the next fill) or one ``bench.fit``, the third of the window where
# there is one, and the counters that cover it.
EXCERPT = {"serve": ("seizure.assemble", {"engine_steps": 1}),
           "train": ("bench.fit", {"fits": 1})}


def excerpt(events: dict, kind: str) -> dict:
    from chipbench import tracing

    name, counters = EXCERPT[kind]
    spans = sorted((s, d) for n, s, d, *_ in events["host"] if n == name)
    if kind == "serve":
        k = min(2, len(spans) - 2)
        lo, hi = spans[k][0], spans[k + 1][0]
    else:
        k = min(2, len(spans) - 1)
        lo, hi = spans[k][0], spans[k][0] + spans[k][1]
    dev = tracing._first(events["devices"])
    ops = [op for op in events["devices"][dev]["ops"]
           if lo < op[1] + op[2] and op[1] < hi]
    paths = sorted({op[3] for op in ops})
    index = {p: i for i, p in enumerate(paths)}
    modules = [[n, s - lo, d] for n, s, d in events["devices"][dev]["modules"]
               if lo < s + d and s < hi]
    host = [[n, s - lo, d, args] for n, s, d, args in events["host"]
            if lo < s + d and s < hi and n != tracing.WINDOW_SPAN]
    return {"counters": counters, "paths": paths, "events": {
        "host": [[tracing.WINDOW_SPAN, 0, hi - lo, {}]] + host,
        "devices": {dev: {
            "ops": [[tracing.short_name(n), s - lo, d, index[path]]
                    for n, s, d, path in ops],
            "modules": modules}}}}


def expand(rec: dict) -> dict:
    """An excerpt's events with each operation's scope path in place of
    its index into ``rec["paths"]``."""
    for dev in rec["events"]["devices"].values():
        for op in dev["ops"]:
            op[3] = rec["paths"][op[3]]
    return rec["events"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--record", help="write an excerpt of the first trace")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))

    from chipbench import cell

    bench, entry, cfg, traffic = cell.load_spec(ROOT, args.workload)
    import jax

    cell.use_cache(ROOT)
    devices = jax.devices()[:entry["chips"]]
    kind = cfg["kind"]
    driver = cell.DRIVERS[kind]
    for seed in args.seeds:
        state = driver.setup(cfg, traffic, seed, devices, cell.log)
        m = cell.measure(driver, state, args.seconds, seed, True, devices)
        prog = m.program
        gaps = sorted(prog.gaps, key=lambda g: -g[1])[:12]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "window_s": m.window.seconds, "counters": m.rec.counters,
            "programs_in_window": m.counter.programs,
            "program_spans": prog.span_count,
            "metrics": cell.per_layer(bench, args.workload, m.context()),
            **coverage(m.trace, prog, m.rec.counters, kind),
            "idle_gaps": [[n, s] for n, s in gaps],
            "gap_s_by_name": _by_name(prog.gaps),
        }), flush=True)
        if args.record:
            pathlib.Path(args.record).write_text(
                json.dumps(excerpt(m.events, kind)))
            args.record = None
        del state, m


def _by_name(gaps) -> dict:
    out: dict = {}
    for n, s in gaps:
        out[n] = out.get(n, 0.0) + s
    return out


if __name__ == "__main__":
    main()
