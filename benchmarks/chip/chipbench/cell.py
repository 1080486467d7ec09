"""One run of one cell, driven by ``BENCHMARK.json`` and the files it names.

A workload names a configuration (``configs/<config>.json``, whose
``kind`` picks the driver: ``serve`` or ``train``) and a traffic mix
(``traffic/<mix>.json``, the parameters the driver's one generator
reads). The end-to-end metrics a run reports are the
ones ``BENCHMARK.json`` gives the cell; the per-layer metrics of a traced
run are read by ``metrics/<name>.py``, each a ``read(ctx)`` that returns
a number or None. Nothing here names a cell, a mix or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import jax

from chipbench import compiles, serve, spans, tracing, train

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
    rec = spans.Recorder(traced)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    setup_s = time.perf_counter() - t_start
    log(f"set-up done in {setup_s:.3f} s; window of {seconds} s")
    try:
        if traced:
            jax.profiler.start_trace(trace_dir)
        with compiles.Counter() as counter:
            window = driver.run_window(state, seconds, seed, rec)
        if traced:
            jax.profiler.stop_trace()
        peak = memory_peak(devices)
        reduction = (tracing.reduce(tracing.load(trace_dir)) if traced
                     else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {window.seconds:.3f} s, counters {rec.counters}, "
        f"in-window {counter.counts}")
    if traced:
        coll = sorted(n for n in reduction.first.op_s
                      if tracing.COLLECTIVE.search(n))
        log(f"trace: {sorted(reduction.devices)}, busy "
            f"{reduction.first.busy_s:.3f} of {reduction.window_s:.3f} s on "
            f"the first chip; programs {reduction.first.module_calls}; "
            f"collective ops {coll[:12]}")

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
    metrics: dict = {}
    if traced:
        ctx = {"spans": {n: (rec.seconds[n], rec.calls[n])
                         for n in rec.seconds},
               "counters": rec.counters, "window_s": window.seconds,
               "trace": reduction}
        for m in bench["per_layer"]:
            if applies(m, workload):
                value = read_metric(m["name"], ctx, root)
                if value is not None:
                    metrics[m["name"]] = value
    else:
        values = dict(driver.end_to_end(window), setup_s=setup_s)
        for m in bench["end_to_end"]:
            if applies(m, workload):
                metrics[m["name"]] = values[m["name"]]
    attempted, failed = driver.attempted_failed(window)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
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
