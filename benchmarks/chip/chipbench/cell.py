"""One run of one cell, driven by ``BENCHMARK.json`` and the files it names.

A workload names a configuration (``configs/<config>.json``, whose
``kind`` picks the driver: ``serve`` or ``train``) and a traffic mix
(``traffic/<mix>.json``, the parameters the driver's one generator
reads). The end-to-end metrics a run reports are the
ones ``BENCHMARK.json`` gives the cell; the per-layer metrics of a traced
run are read by ``metrics/<name>.py``, each a ``read(ctx)`` that returns
a number or None. A reader's ``ctx`` holds ``spans`` and ``counters``
(the benchmark's own, ``spans.Recorder``), ``window_s``, ``trace``
(``tracing.reduce``: device time and the benchmark's spans) and
``program`` (``program_trace.reduce``: the program's ``seizure.*`` spans
with their args, and device time per ``jax.named_scope``, the scopes
taken from the compiled HLO of what the window ran, ``hlo_text`` of the
driver). Nothing here names a cell, a mix or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import NamedTuple

import jax

from chipbench import compiles, program_trace, serve, spans, tracing, train

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
HOME = BENCH_DIR.relative_to(BENCH_DIR.parents[1])   # benchmarks/chip
DRIVERS = {"serve": serve, "train": train}


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """Progress on stderr, stamped with the seconds since start-up."""
    print(f"[bench {time.perf_counter() - _T0:8.3f}] {msg}", file=sys.stderr,
          flush=True)


def load_spec(root: pathlib.Path, workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, workload entry, configuration, traffic) by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / HOME / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, traffic


def use_cache(root: pathlib.Path) -> None:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` in the checkout; every program
    cached, and metadata part of the key, so that a program loaded from
    the cache carries this commit's scopes and not an older one's."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def read_metric(name: str, ctx: dict, root: pathlib.Path = BENCH_DIR.parents[1]):
    path = root / HOME / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Measured(NamedTuple):
    window: object                 # the driver's Window
    rec: spans.Recorder
    counter: compiles.Counter      # what compiled inside the window
    peak: int                      # memory_peak_bytes after the window
    trace: tracing.Reduction | None
    program: program_trace.Program | None
    events: dict | None            # program_trace.load's events, scoped

    def context(self) -> dict:
        """What the per-layer metrics' readers are handed."""
        return {"spans": {n: (self.rec.seconds[n], self.rec.calls[n])
                          for n in self.rec.seconds},
                "counters": self.rec.counters,
                "window_s": self.window.seconds,
                "trace": self.trace, "program": self.program}


def measure(driver, state, seconds: float, seed: int, traced: bool,
            devices: list) -> Measured:
    """The window; traced, the profiler's trace of it reduced twice: by
    ``tracing`` and, with the scopes of the compiled HLO of the driver's
    program attached, by ``program_trace``."""
    rec = spans.Recorder(traced)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    reduction = prog = events = None
    try:
        if traced:
            jax.profiler.start_trace(trace_dir)
        with compiles.Counter() as counter:
            window = driver.run_window(state, seconds, seed, rec)
        if traced:
            jax.profiler.stop_trace()
            log("trace written")
        peak = memory_peak(devices)
        if traced:
            reduction = tracing.reduce(tracing.load(trace_dir))
            events = program_trace.load(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {window.seconds:.3f} s, counters {rec.counters}, "
        f"in-window {counter.counts}")
    if traced:
        scoped = program_trace.attach_scopes(events, driver.hlo_text(state))
        _, prog = program_trace.reduce(events)
        coll = sorted(n for n in reduction.first.op_s
                      if tracing.COLLECTIVE.search(n))
        log(f"trace: {sorted(reduction.devices)}, busy "
            f"{reduction.first.busy_s:.3f} of {reduction.window_s:.3f} s on "
            f"the first chip; programs {reduction.first.module_calls}; "
            f"collective ops {coll[:12]}; {scoped} operations scoped")
    return Measured(window, rec, counter, peak, reduction, prog, events)


def per_layer(bench: dict, workload: str, ctx: dict,
              root: pathlib.Path = BENCH_DIR.parents[1]) -> dict:
    """The cell's per-layer metrics that its readers find."""
    out = {}
    for m in bench["per_layer"]:
        if applies(m, workload):
            value = read_metric(m["name"], ctx, root)
            if value is not None:
                out[m["name"]] = value
    return out


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        traced: bool, devices: list, t_start: float,
        spec: tuple | None = None, control: bool = False) -> dict:
    """Set up, measure, check. Returns the result object (the last
    stdout line) with the compared numbers under ``checks``. With
    ``control`` the comparison is handed the control's answers (the
    reference in bfloat16) in place of the program's."""
    bench, cell, cfg, traffic = spec or load_spec(root, workload)
    driver = DRIVERS[cfg["kind"]]
    state = driver.setup(cfg, traffic, seed, devices, log)
    setup_s = time.perf_counter() - t_start
    log(f"set-up done in {setup_s:.3f} s; window of {seconds} s")
    measured = measure(driver, state, seconds, seed, traced, devices)
    window, counter, reduction = (measured.window, measured.counter,
                                  measured.trace)

    t_check = time.perf_counter()
    numbers = driver.check(state, window, seed, control)
    limits = cfg["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    info = {k: v for k, v in numbers.items() if k not in limits}
    info["programs_in_window"] = counter.programs
    info["check_s"] = time.perf_counter() - t_check
    log(f"check: {json.dumps(info)}")

    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    if traced:
        metrics = per_layer(bench, workload, measured.context(), root)
    else:
        values = dict(driver.end_to_end(window), setup_s=setup_s)
        metrics = {e["name"]: values[e["name"]] for e in bench["end_to_end"]
                   if applies(e, workload)}
    attempted, failed = driver.attempted_failed(window)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": measured.peak}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": attempted,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": device,
    }
    if traced:
        device["busy_s"] = reduction.busy_s()
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    result["checks"] = checks
    return result
