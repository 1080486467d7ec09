#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans, counters and
scopes: one traced window per seed, on the chip.

  python3 benchmarks/chip/split.py --workload serve.reconnect \
      --seeds 7 8 --seconds 30

Runs the cell's set-up and one traced window as ``run.py --trace 1``
does, without the comparison, and reads the trace through ``tracing``
(the accepted per-layer metrics, by their own readers) and through
``program_trace`` (the program's ``seizure.*`` spans with their args, and
the device time of each ``jax.named_scope``). Prints one JSON object per
seed: the accepted metrics, the split under the names of the metrics
that would read it, the sums that show whether the spans and scopes
cover their layers, the longest idle gaps named by program spans, and
the compilations in the window.

The chip's operation events carry no scope path (PERF.md section 6), so
each operation of the step or the fit is given that of its instruction
in the compiled program's HLO text (``program_trace.attach_scopes``). So
that this text holds the program's scopes, the run makes metadata part
of the compilation cache key: an entry of an older commit would bring
back its own.

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
import os
import pathlib
import shutil
import sys
import tempfile

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]

SERVE_SPANS = ("fill", "assemble", "put", "dispatch", "readback", "events")
SERVE_SCOPES = ("mspca", "eigh", "wpd", "vote", "ring")
TRAIN_SCOPES = ("featurize", "mspca", "eigh", "wpd", "moments", "rotate",
                "grow", "gather")


def serve_split(red, prog, counters: dict) -> dict:
    steps = counters["engine_steps"]
    _, execs = red.program("_engine_step_megabatch")
    chunks = prog.arg("seizure.assemble", "chunks")
    scope = prog.first_scope_s()
    spans = {n: prog.span_s.get("seizure." + n, 0.0) * 1e3 / steps
             for n in SERVE_SPANS}
    scopes = {n: scope.get(n, 0.0) * 1e3 / execs for n in SERVE_SCOPES}
    h2d = prog.arg("seizure.put", "bytes") + prog.arg("seizure.fill",
                                                       "h2d_bytes")
    metrics = {f"{n}_ms_per_step.replay": spans[n]
               for n in ("fill", "assemble", "put", "readback")}
    metrics.update({
        "queue_wait_ms.replay":
            prog.arg("seizure.assemble", "queue_wait_s") * 1e3 / chunks,
        "h2d_mb_per_chunk.replay": h2d / 1e6 / chunks,
    })
    metrics.update({f"{n}_device_ms.replay": scopes[n]
                    for n in ("mspca", "wpd", "vote")})
    return {"metrics": metrics, "span_ms_per_step": spans,
            "scope_ms_per_step": scopes,
            "spans_sum_ms_per_step": sum(spans.values()),
            "stages_sum_ms_per_step": sum(scopes[n] for n in (
                "mspca", "wpd", "vote", "ring")),
            "chunks_taken": chunks, "step_executions": execs,
            "span_args": prog.span_args}


def train_split(red, prog, counters: dict) -> dict:
    fits = counters["fits"]
    scope = prog.first_scope_s()
    scopes = {n: scope.get(n, 0.0) * 1e3 / fits for n in TRAIN_SCOPES}
    return {"metrics": {f"{n}_device_ms.train": scopes[n]
                        for n in ("featurize", "rotate", "grow")},
            "scope_ms_per_fit": scopes,
            "stages_sum_ms_per_fit": sum(scopes[n] for n in (
                "featurize", "moments", "rotate", "grow", "gather"))}


SPLITS = {"serve": serve_split, "train": train_split}


def serve_hlo(fleet) -> str:
    """The compiled HLO text of the engine's step, as the window ran it."""
    import jax
    import jax.numpy as jnp
    from repro.serving import api
    from repro.signal import eeg_data

    eng, prog = fleet.engine, fleet.engine.program
    b, d = eng.max_batch, eng.replay_depth
    chunks = jax.ShapeDtypeStruct((b, d, eng.chunk_windows,
                                   eeg_data.N_CHANNELS, eeg_data.WINDOW),
                                  jnp.float32)
    return api._jit_engine_step_megabatch.lower(
        eng._state, chunks, jax.ShapeDtypeStruct((b, d), jnp.int32),
        prog.packed, prog.feat_mean, prog.feat_std, cfg=prog.cfg,
        use_pallas=eng.use_forest_kernel).compile().as_text()


def train_hlo(job) -> str:
    """The compiled HLO text of the fit, as the window ran it."""
    return job.fit.lower(job.key, *job.data[0]).compile().as_text()


HLO_TEXT = {"serve": serve_hlo, "train": train_hlo}


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

    from chipbench import cell, compiles, program_trace, spans

    bench, entry, cfg, traffic = cell.load_spec(ROOT, args.workload)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    devices = jax.devices()[:entry["chips"]]
    driver = cell.DRIVERS[cfg["kind"]]
    for seed in args.seeds:
        state = driver.setup(cfg, traffic, seed, devices, cell.log)
        rec = spans.Recorder(True)
        trace_dir = tempfile.mkdtemp(prefix="split-trace-")
        try:
            jax.profiler.start_trace(trace_dir)
            with compiles.Counter() as counter:
                window = driver.run_window(state, args.seconds, seed, rec)
            jax.profiler.stop_trace()
            events = program_trace.load(trace_dir)
            scoped = program_trace.attach_scopes(
                events, HLO_TEXT[cfg["kind"]](state))
            red, prog = program_trace.reduce(events)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"spans": {n: (rec.seconds[n], rec.calls[n])
                         for n in rec.seconds},
               "counters": rec.counters, "window_s": window.seconds,
               "trace": red}
        accepted = {m["name"]: cell.read_metric(m["name"], ctx)
                    for m in bench["per_layer"]
                    if cell.applies(m, args.workload)}
        gaps = sorted(prog.gaps, key=lambda g: -g[1])[:12]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "window_s": window.seconds, "counters": rec.counters,
            "programs_in_window": counter.programs,
            "program_spans": sum(prog.span_count.values()),
            "scoped_ops": scoped,
            "accepted": accepted,
            **SPLITS[cfg["kind"]](red, prog, rec.counters),
            "idle_gaps": [[n, s] for n, s in gaps],
            "gap_s_by_name": _by_name(prog.gaps),
        }), flush=True)
        if args.record:
            pathlib.Path(args.record).write_text(
                json.dumps(excerpt(events, cfg["kind"])))
            args.record = None
        del state, events


def _by_name(gaps) -> dict:
    out: dict = {}
    for n, s in gaps:
        out[n] = out.get(n, 0.0) + s
    return out


if __name__ == "__main__":
    main()
