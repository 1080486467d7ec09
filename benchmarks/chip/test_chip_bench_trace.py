"""CPU checks of the chip benchmark's yardstick: the trace reduction on a
synthetic and a recorded trace, the metric readers, and BENCHMARK.json
against the rules that every cell, mix and metric is found by name."""

from __future__ import annotations

import json
import pathlib
import re
import shutil
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))

from chipbench import cell, peaks, tracing  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def synthetic() -> dict:
    ops = [["fusion.1", 1000, 2000], ["fusion.2", 2000, 2000],
           ["all-gather.3", 6000, 1000], ["fusion.1", 9000, 3000]]
    modules = [["jit__engine_step_megabatch", 1000, 3000],
               ["jit_other", 6000, 1000]]
    host = [["bench.window", 1000, 10000], ["bench.poll", 900, 4100],
            ["bench.arrivals", 4000, 5800], ["bench.push", 4500, 5000]]
    return {"host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_reduce_busy_union_programs_and_collectives():
    red = tracing.reduce(synthetic())
    assert red.window_s == pytest.approx(10000e-9)
    # [1000, 4000] + [6000, 7000] + [9000, 11000] (clipped at the window).
    assert red.first.busy_s == pytest.approx(6000e-9)
    assert red.busy_s() == pytest.approx(6000e-9)
    assert red.first.collective_s == pytest.approx(1000e-9)
    assert red.program("_engine_step_megabatch") == (pytest.approx(3000e-9), 1)
    assert red.program("nothing") == (0, 0)
    assert red.first.op_s["fusion.1"] == pytest.approx(4000e-9)
    assert red.host_count == {"bench.poll": 1, "bench.arrivals": 1,
                              "bench.push": 1}


def test_reduce_names_idle_gaps_by_host_span():
    red = tracing.reduce(synthetic())
    # [4000, 6000]: bench.arrivals covers all of it; [7000, 9000]:
    # arrivals and push tie, the inner span (push) names it.
    assert sorted(red.gaps) == [("bench.arrivals", pytest.approx(2000e-9)),
                                ("bench.push", pytest.approx(2000e-9))]
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(4000e-9)]
    assert len(bd["idle_gaps"]) == 2


def test_reduce_needs_a_window_and_a_device():
    events = synthetic()
    with pytest.raises(RuntimeError):
        tracing.reduce({"host": events["host"][1:],
                        "devices": events["devices"]})
    with pytest.raises(RuntimeError):
        tracing.reduce({"host": events["host"], "devices": {}})


def test_reduce_recorded_chip_trace():
    events = json.loads((BENCH_DIR / "testdata" / "trace_small.json").read_text())
    red = tracing.reduce(events)
    assert 0 < red.first.busy_s <= red.window_s
    seconds, calls = red.program("_engine_step_megabatch")
    assert calls > 0 and 0 < seconds <= red.window_s
    bd = red.breakdown()
    assert bd["device_ops"] and bd["idle_gaps"]
    assert all(s > 0 for _, s in bd["device_ops"] + bd["idle_gaps"])
    assert sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s - red.first.busy_s, rel=1e-6)


def ctx_for(reduction, **counters) -> dict:
    return {"spans": {"bench.push": (0.5, 10), "bench.poll": (2.0, 4)},
            "counters": counters, "window_s": 10.0, "trace": reduction}


def test_metric_readers():
    red = tracing.reduce(synthetic())
    ctx = ctx_for(red, chunks_pushed=100, engine_steps=8, fits=2)
    assert cell.read_metric("push_ms_per_chunk.replay", ctx) == pytest.approx(5.0)
    assert cell.read_metric("poll_ms_per_step.replay", ctx) == pytest.approx(250.0)
    assert cell.read_metric("step_device_ms.replay", ctx) == pytest.approx(3e-3)
    assert cell.read_metric("idle_share.replay", ctx) == pytest.approx(40.0)
    assert cell.read_metric("idle_share.train", ctx) == pytest.approx(40.0)
    assert cell.read_metric("fit_device_ms.train", ctx) == pytest.approx(3e-3)
    assert cell.read_metric("collective_share.train", ctx) == pytest.approx(
        100.0 / 6.0)
    empty = ctx_for(None)
    for name in ("push_ms_per_chunk.replay", "poll_ms_per_step.replay",
                 "step_device_ms.replay", "idle_share.replay",
                 "fit_device_ms.train", "collective_share.train"):
        assert cell.read_metric(name, empty) is None, name


def test_engine_step_reader_fails_loudly_without_the_step():
    events = synthetic()
    events["devices"]["/device:TPU:0"]["modules"] = [["jit_other", 6000, 1000]]
    with pytest.raises(RuntimeError):
        cell.read_metric("step_device_ms.replay",
                         ctx_for(tracing.reduce(events)))


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("cpu")


def test_benchmark_json_names_units_and_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert (ROOT / bench["command"][1]).is_file()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]] + list(e2e) + list(layer))
    assert len(set(names)) == len(names)
    for name in names:
        assert NAME.match(name), name
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert "setup_s" in e2e
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["kind"] in cell.DRIVERS
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (BENCH_DIR / "traffic" / f"{w['traffic']}.json").is_file()
        cell.load_spec(ROOT, w["name"])
        reported = [n for n, m in e2e.items() if cell.applies(m, w["name"])]
        assert "setup_s" in reported and len(reported) >= 2, w["name"]
        assert any(cell.applies(m, w["name"]) for m in layer.values())
    for name, m in layer.items():
        assert (BENCH_DIR / "metrics" / f"{name}.py").is_file()
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert cell.applies(e2e[m["moves"]], w), (name, w)


def test_a_cell_mix_and_metric_are_added_as_files(tmp_path):
    """A new cell needs a BENCHMARK.json entry and new files only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "serve.trickle", "config": "freiburg3-serve",
        "traffic": "trickle", "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({
        "name": "pushes.trickle", "unit": "1", "better": "higher",
        "source": "host_clock", "layer": "sessions", "moves": "chunks_per_s",
        "workloads": ["serve.trickle"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and "serve.reconnect" in m["workloads"]:
            m["workloads"].append("serve.trickle")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    chip = root / "benchmarks" / "chip"
    (chip / "traffic" / "trickle.json").write_text(json.dumps(
        {"arrivals": "open", "rate": 1, "period_s": 480,
         "pool": {"timelines": 1, "interictal_chunks": 1}}))
    (chip / "metrics" / "pushes.trickle.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('chunks_pushed')\n")
    _, entry, cfg, traffic = cell.load_spec(root, "serve.trickle")
    assert entry["traffic"] == "trickle" and traffic["arrivals"] == "open"
    assert cfg["kind"] == "serve"
    # The reader is found by its name, beside the others.
    assert cell.read_metric("pushes.trickle",
                            {"counters": {"chunks_pushed": 3}}, root) == 3
