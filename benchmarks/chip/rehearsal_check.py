"""Runs of both cells rehearsed on the CPU at tiny sizes, through the
harness's own functions (``cell.run``: set-up, the window's traffic, the
plain reference and the comparison), and new traffic, configurations and
cells added as data files alone.

  python -m pytest -q benchmarks/chip/rehearsal_check.py

The serving cell runs in this process; the training cell runs in a
child process on 4 virtual devices (forced before JAX starts), once as
the mesh fit and once with the configuration's ``fit`` switched to the
``n_shards=4`` emulation on one device. About a minute alone; not part
of the default test collection, since under the default run's six
workers its compilations take minutes. The command itself refuses to
run without a TPU (test_chip_bench_command.py); planted faults and the
controls are in faults_check.py.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from chipbench import cell, rehearsal  # noqa: E402

SEED = 2**35 + 21


def _run(root, workload):
    spec = rehearsal.tiny_spec(root, workload)
    return cell.run(root, workload, SEED, 1.0, False, jax.devices()[:1],
                    time.perf_counter(), spec=spec)


def test_serving_cell_rehearsal():
    res = _run(ROOT, "serve.reconnect")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"chunks_per_s", "p99_chunk_ms", "setup_s"}
    assert res["metrics"]["chunks_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"window_disagree", "vote_mismatch",
                                  "alarm_mismatch", "unscored"}


# Mixes and engine options that no existing cell uses, each added as data
# files; the last item names the program call that shows it took effect.
NEW_MIXES = {
    "burst-windows": (
        {"arrivals": "open", "rate": 4, "period_s": 3, "push": "windows",
         "burst": {"on_s": 0.5, "off_s": 0.5},
         "pool": {"timelines": 1, "interictal_chunks": 1}},
        {}, "StreamSession.push"),
    "churn": (
        {"arrivals": "closed", "fleet": 6, "outstanding": 2,
         "backlog_chunks": [1, 2], "churn": 1.0,
         "pool": {"timelines": 1, "interictal_chunks": 1}},
        {}, "SeizureEngine.close_session"),
    "budget": (
        {"arrivals": "closed", "fleet": 6, "outstanding": 2,
         "backlog_chunks": [1, 2],
         "pool": {"timelines": 1, "interictal_chunks": 1}},
        {"latency_budget_s": 0.25}, "SeizureEngine.poll"),
    "mesh": (
        {"arrivals": "closed", "fleet": 6, "outstanding": 2,
         "backlog_chunks": [1, 2],
         "pool": {"timelines": 1, "interictal_chunks": 1}},
        {"mesh": True}, "mesh.make_data_mesh"),
}


@pytest.mark.parametrize("mix", sorted(NEW_MIXES))
def test_new_traffic_is_added_as_data_files(tmp_path, monkeypatch, mix):
    from repro.launch import mesh
    from repro.serving import api

    traffic, engine, counted = NEW_MIXES[mix]
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, chip,
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    (chip / "traffic" / f"{mix}.json").write_text(json.dumps(traffic))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg["name"] = "serve-variant"
    cfg["engine"].update(engine)
    (chip / "configs" / "serve-variant.json").write_text(json.dumps(cfg))
    bench["configs"].append({
        "name": "serve-variant", "source": "https://arxiv.org/abs/1712.06071",
        "file": "benchmarks/chip/configs/serve-variant.json", "reduced": [],
        "why": "throwaway"})
    name = f"serve.{mix}"
    bench["workloads"].append({"name": name, "config": "serve-variant",
                               "traffic": mix, "chips": 1, "why": "throwaway"})
    for m in bench["end_to_end"]:
        if "serve.reconnect" in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    owner_name, attr = counted.split(".")
    owner = {"SeizureEngine": api.SeizureEngine,
             "StreamSession": api.StreamSession, "mesh": mesh}[owner_name]
    calls = []
    real = getattr(owner, attr)

    def spy(*a, **k):
        calls.append(a)
        return real(*a, **k)

    monkeypatch.setattr(owner, attr, spy)
    res = _run(root, name)
    assert res["correct"] is True, (mix, res["checks"])
    assert res["attempted"] > 0 and set(res["metrics"]) == {
        "chunks_per_s", "p99_chunk_ms", "setup_s"}
    assert calls, f"{counted} never called"
    if mix == "burst-windows":
        # The window's pushes carry one (3, 2048) window each.
        assert any(a[1].ndim == 2 for a in calls)


CHILD = r"""
import json, pathlib, sys, time
root = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(root / "src"), str(root / "benchmarks" / "chip")]
import jax
from chipbench import cell, rehearsal
spec = rehearsal.tiny_spec(root, "train.mapreduce4")
res = cell.run(root, "train.mapreduce4", int(sys.argv[2]), 0.5, False,
               jax.devices()[:4], time.perf_counter(), spec=spec)
print(json.dumps(res))
spec[2]["fit"] = {"mesh": False, "shards": 4}
res = cell.run(root, "train.mapreduce4", int(sys.argv[2]), 0.5, False,
               jax.devices()[:1], time.perf_counter(), spec=spec)
print(json.dumps(res))
"""


def test_training_cell_rehearsal_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), str(2**35 + 21)], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    mesh, emulated = map(json.loads, out.stdout.strip().splitlines()[-2:])
    for res, chips in ((mesh, 4), (emulated, 1)):
        assert res["correct"] is True, res["checks"]
        assert res["device"]["count"] == chips and res["attempted"] > 0
        assert set(res["metrics"]) == {"setup_s", "train_windows_per_s"}
        assert set(res["checks"]) == {"moment_gap", "heldout_disagree"}
