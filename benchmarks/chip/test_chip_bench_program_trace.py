"""CPU checks of the reduction of the program's own trace marks
(``chipbench/program_trace.py``): ``seizure.*`` host spans with their
args, device time per ``jax.named_scope`` segment, idle gaps put down to
program spans; on a synthetic trace, on a recorded chip trace of each
cell, and on the older recorded trace that has no program marks; and the
per-layer metrics that read them (``metrics/<name>.py``)."""

from __future__ import annotations

import functools
import json
import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))

import split  # noqa: E402
from chipbench import cell, program_trace, tracing  # noqa: E402

STEP = "jit(_engine_step_megabatch)"
EIGH = f"{STEP}/vmap(mspca)/vmap(jit(denoise_windows))/jit(denoise)/eigh/jit(eigh)/eigh"
RECORDED = BENCH_DIR / "testdata" / "trace_program_small.json"
ROOT = BENCH_DIR.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def synthetic() -> dict:
    host = [
        ["bench.window", 1000, 10000, {}], ["bench.poll", 900, 4100, {}],
        ["seizure.fill", 1000, 100, {"evictions": 1, "admissions": 2,
                                     "d2h_bytes": 50, "h2d_bytes": 100}],
        ["seizure.assemble", 1100, 400, {"chunks": 3, "queue_wait_s": 1.5}],
        ["seizure.put", 1500, 100, {"bytes": 400}],
        ["seizure.dispatch", 1600, 50, {}],
        ["seizure.readback", 1650, 2850, {"bytes": 24}],
        ["seizure.events", 4500, 200, {}],
        ["seizure.fill", 4700, 100, {"evictions": 0, "admissions": 1,
                                     "d2h_bytes": 0, "h2d_bytes": 60}],
        # Half inside the window: half its time counts, all its args.
        ["seizure.fill", 10900, 200, {"evictions": 2, "admissions": 0,
                                      "d2h_bytes": 10, "h2d_bytes": 0}],
    ]
    ops = [
        # A while and its body: their times overlap.
        ["while.1", 1700, 2300, f"{STEP}/ring/while"],
        ["fusion.2", 1800, 700, f"{STEP}/ring/while/body/add"],
        ["fusion.3", 3000, 500, f"{STEP}/ring/while/body/mul"],
        ["custom-call.4", 4000, 300, EIGH],
        ["fusion.5", 4300, 100, f"{STEP}/vote/gather"],
        ["fusion.6", 4400, 50, "gather"],
    ]
    modules = [["jit__engine_step_megabatch", 1700, 2750]]
    return {"host": host,
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}


def test_spans_args_and_the_window():
    red, prog = program_trace.reduce(synthetic())
    assert prog.span_count == {
        "seizure.fill": 3, "seizure.assemble": 1, "seizure.put": 1,
        "seizure.dispatch": 1, "seizure.readback": 1, "seizure.events": 1}
    assert prog.span_s["seizure.fill"] == pytest.approx(300e-9)
    assert prog.span_s["seizure.readback"] == pytest.approx(2850e-9)
    assert prog.span_args["seizure.fill"] == {
        "evictions": 3, "admissions": 3, "d2h_bytes": 60, "h2d_bytes": 160}
    assert prog.arg("seizure.assemble", "queue_wait_s") == 1.5
    assert prog.arg("seizure.dispatch", "bytes") == 0
    # The benchmark's own spans stay where they were.
    assert red.host_count == {"bench.poll": 1}


def test_scope_time_is_the_union_of_its_operations():
    _, prog = program_trace.reduce(synthetic())
    scope = prog.first_scope_s()
    # The while covers its body: 2300 ns, not 2300 + 700 + 500.
    assert scope["ring"] == pytest.approx(2300e-9)
    assert scope["while"] == scope["body"] == pytest.approx(1200e-9)
    assert scope["mspca"] == scope["eigh"] == pytest.approx(300e-9)
    assert scope["vote"] == pytest.approx(100e-9)
    # A primitive named like a scope is not a scope.
    assert "gather" not in scope
    assert scope["_engine_step_megabatch"] == pytest.approx(2700e-9)


def test_segments_peel_transforms_and_drop_the_primitive():
    assert program_trace.segments(EIGH) == {
        "_engine_step_megabatch", "mspca", "denoise_windows", "denoise",
        "eigh"}
    assert program_trace.segments("gather") == set()
    assert program_trace.segments("") == set()
    assert program_trace.segments("jit(f)/vmap()/add") == {"f"}


def test_idle_time_is_put_down_to_the_innermost_program_span():
    red, prog = program_trace.reduce(synthetic())
    assert prog.gaps == [
        ("seizure.fill", pytest.approx(100e-9)),
        ("seizure.assemble", pytest.approx(400e-9)),
        ("seizure.put", pytest.approx(100e-9)),
        ("seizure.dispatch", pytest.approx(50e-9)),
        ("seizure.readback", pytest.approx(50e-9)),
        ("seizure.readback", pytest.approx(50e-9)),
        ("seizure.events", pytest.approx(200e-9)),
        ("seizure.fill", pytest.approx(100e-9)),
        ("bench.poll", pytest.approx(200e-9)),
        ("other", pytest.approx(5900e-9)),
        ("seizure.fill", pytest.approx(100e-9)),
    ]
    assert sum(s for _, s in prog.gaps) == pytest.approx(
        red.window_s - red.first.busy_s)
    # tracing's own gaps, from the benchmark's spans alone, are unchanged.
    assert red.gaps == tracing.reduce(_plain(synthetic())).gaps


def _plain(events: dict) -> dict:
    return {"host": [h[:3] for h in events["host"]],
            "devices": {d: {"ops": [o[:3] for o in dev["ops"]],
                            "modules": dev["modules"]}
                        for d, dev in events["devices"].items()}}


HLO = f"""HloModule jit__engine_step_megabatch, is_scheduled=true

%fc (a: f32[2]) -> f32[2] {{
  %a = f32[2]{{0}} parameter(0)
  ROOT %scatter.1 = f32[2]{{0}} scatter(%a, %a, %a), metadata={{op_name="{STEP}/grow/jit(g)/scatter-add"}}
}}

ENTRY %main.9 (p: f32[2]) -> f32[2] {{
  %custom-call.4 = f32[2]{{0}} custom-call(f32[2]{{0}} %p), custom_call_target="Eigh", metadata={{op_name="{EIGH}" source_file="pca.py" source_line=53}}
  %fusion.6 = f32[2]{{0}} fusion(f32[2]{{0}} %p), kind=kCustom, calls=%fc
  ROOT %fusion.5 = f32[2]{{0}} fusion(f32[2]{{0}} %custom-call.4), kind=kLoop, calls=%fc, metadata={{op_name="{STEP}/vote/gather"}}
}}
"""


def test_scopes_come_from_the_hlo_text_of_the_program_that_ran():
    ops = [["%custom-call.4 = f32[2]{0} custom-call(...)", 100, 10],
           ["fusion.5", 120, 10],
           ["fusion.5", 300, 10],            # another program's fusion.5
           ["fusion.7", 130, 10],            # not in the text
           ["fusion.6", 140, 20]]            # no op_name of its own
    modules = [["jit__engine_step_megabatch(42)", 90, 100],
               ["jit__splice_state(7)", 290, 50]]
    events = {"host": [["bench.window", 0, 1000, {}]],
              "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}}
    assert program_trace.attach_scopes(events, HLO) == 3
    assert [op[3] for op in ops] == [EIGH, f"{STEP}/vote/gather", "", "",
                                     f"{STEP}/grow/jit(g)/fusion.6"]
    _, prog = program_trace.reduce(events)
    assert prog.first_scope_s()["mspca"] == pytest.approx(10e-9)
    assert prog.first_scope_s()["vote"] == pytest.approx(10e-9)
    assert prog.first_scope_s()["grow"] == pytest.approx(20e-9)


def test_a_trace_without_program_marks_reduces_as_before():
    events = json.loads((BENCH_DIR / "testdata" / "trace_small.json")
                        .read_text())
    red, prog = program_trace.reduce(events)
    assert red == tracing.reduce(events)
    assert prog.span_s == {} and prog.first_scope_s() == {}
    assert sum(s for _, s in prog.gaps) == pytest.approx(
        red.window_s - red.first.busy_s)
    ctx = {"spans": {"bench.poll": (2.0, 4)}, "window_s": 10.0,
           "counters": {"engine_steps": 8, "fits": 2}, "trace": red}
    # The accepted readers' values on this trace, as before this reader.
    assert cell.read_metric("step_device_ms.replay", ctx) == pytest.approx(
        1492.516397333333, rel=1e-9)
    assert cell.read_metric("idle_share.replay", ctx) == pytest.approx(
        25.63714275065223, rel=1e-9)
    assert cell.read_metric("fit_device_ms.train", ctx) == pytest.approx(
        2238.9031705, rel=1e-9)


def test_excerpt_is_one_step_under_its_own_window():
    events = synthetic()
    events["host"] += [["seizure.assemble", 6000, 100, {"chunks": 1,
                                                         "queue_wait_s": 0.5}]]
    rec = json.loads(json.dumps(split.excerpt(events, "serve")))
    assert rec["counters"] == {"engine_steps": 1}
    assert rec["events"]["host"][0] == ["bench.window", 0, 4900, {}]
    red, prog = program_trace.reduce(split.expand(rec))
    assert prog.span_count["seizure.assemble"] == 1
    assert red.program("_engine_step_megabatch") == (pytest.approx(2750e-9), 1)
    assert prog.first_scope_s()["ring"] == pytest.approx(2300e-9)


@functools.cache
def _recorded(workload: str) -> dict:
    """A reader's ``ctx`` for one step or one fit of a traced run on a TPU
    v5 lite (``split.py --record``)."""
    rec = json.loads(RECORDED.read_text())[workload]
    red, prog = program_trace.reduce(split.expand(rec))
    return {"spans": {n: (s, red.host_count[n]) for n, s in red.host_s.items()},
            "counters": rec["counters"], "window_s": red.window_s,
            "trace": red, "program": prog}


@pytest.mark.parametrize("workload", ["serve.reconnect", "train.mapreduce4"])
def test_recorded_chip_trace_gives_every_split_metric(workload):
    assert RECORDED.stat().st_size < 1 << 20
    ctx = _recorded(workload)
    got = cell.per_layer(BENCH, workload, ctx)
    assert set(PROGRAM_READINGS[workload]) <= set(got)
    assert all(got[n] > 0 for n in PROGRAM_READINGS[workload]), got
    kind = "serve" if workload.startswith("serve") else "train"
    cov = split.coverage(ctx["trace"], ctx["program"], ctx["counters"], kind)
    # The spans and scopes cover their layers.
    if kind == "serve":
        seconds, calls = ctx["trace"].program("_engine_step_megabatch")
        assert calls == 1
        assert 0.95 * seconds * 1e3 <= cov["stages_sum_ms"] <= seconds * 1e3
        poll = got["poll_ms_per_step.replay"]
        assert 0.95 * poll <= cov["spans_sum_ms"] <= poll
        stages = sum(got[f"{n}_device_ms.replay"]
                     for n in ("mspca", "wpd", "vote"))
        assert stages >= 0.98 * got["step_device_ms.replay"]
        host = sum(got[f"{n}_ms_per_step.replay"]
                   for n in ("fill", "assemble", "put", "readback"))
        assert host <= poll
    else:
        busy = got["fit_device_ms.train"]
        assert 0.95 * busy <= cov["stages_sum_ms"] <= busy
        stages = sum(got[f"{n}_device_ms.train"]
                     for n in ("featurize", "rotate", "grow"))
        assert stages >= 0.95 * busy
        assert got["grow_device_ms.train"] > 30


# The split of the recorded trace as its arithmetic gave it before the
# metrics' readers took it over (ms, or MB per chunk).
PROGRAM_READINGS = {
    "serve.reconnect": {
        "fill_ms_per_step.replay": 55.452412,
        "assemble_ms_per_step.replay": 394.545755,
        "put_ms_per_step.replay": 6.66298,
        "readback_ms_per_step.replay": 1472.269353,
        "queue_wait_ms.replay": 13386.909989726535,
        "h2d_mb_per_chunk.replay": 1.5414721142857142,
        "mspca_device_ms.replay": 1396.988726,
        "wpd_device_ms.replay": 32.670432,
        "vote_device_ms.replay": 5.121087,
    },
    "train.mapreduce4": {
        "featurize_device_ms.train": 167.118617,
        "rotate_device_ms.train": 99.211844,
        "grow_device_ms.train": 64.115084,
    },
}
READERS = [(w, n) for w, names in PROGRAM_READINGS.items() for n in names]


@pytest.mark.parametrize("workload,name", READERS, ids=[n for _, n in READERS])
def test_reader_gives_the_split_of_the_recorded_trace(workload, name):
    value = cell.read_metric(name, _recorded(workload))
    assert value == pytest.approx(PROGRAM_READINGS[workload][name], rel=1e-12)


@pytest.mark.parametrize("workload,name", READERS, ids=[n for _, n in READERS])
def test_reader_fails_loudly_without_its_mark(workload, name):
    ctx = dict(_recorded(workload))
    # Untraced: nothing to read.
    assert cell.read_metric(name, dict(ctx, program=None)) is None
    # Traced, but the program wrote no spans and its HLO gave no scopes.
    bare = ctx["program"]._replace(
        span_s={}, span_count={}, span_args={},
        scope_s={d: {} for d in ctx["program"].scope_s})
    with pytest.raises(RuntimeError, match="no "):
        cell.read_metric(name, dict(ctx, program=bare))

