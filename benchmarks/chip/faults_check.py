"""Whole runs of both cells at tiny sizes on the CPU with the timed path
broken underneath, and with the control in the program's place: each
has to come out not correct.

  python -m pytest -q benchmarks/chip/faults_check.py

Drives tiny runs (``rehearsal.tiny_spec`` through ``cell.run``: traffic,
the system under test, the plain reference and the comparison; the
training cell on 4 virtual devices in a child process). Serving: the
engine step returns its state unchanged, half of the batch is left out
(its chunks zeroed), an answer is altered where it is produced (one
window label flipped), and the control (``cell.run(control=True)``: the
reference in bfloat16 hands in the sampled chunks' labels). Training:
the fit returns an untrained forest, half of each shard's rows are left
out, the exchange of moments between shards is left out, an answer is
altered (one feature's mean moved by one deviation), and the control.
Sound tiny runs of both cells are in rehearsal_check.py. About two and
a half minutes; not part of the default test collection.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import cell, rehearsal  # noqa: E402

SEED = 2**35 + 21


def _serve_spec():
    """The tiny serving cell with the configuration's own forest, grown
    on more patients, and a few more backlogs, so that a broken half of
    a batch is sampled."""
    spec = rehearsal.tiny_spec(ROOT, "serve.reconnect")
    spec[2]["pipeline"]["forest"].update(
        cell.load_spec(ROOT, "serve.reconnect")[2]["pipeline"]["forest"])
    spec[2]["check"]["sample_chunks"] = 8
    spec[2]["served_forest"].update(patients=6)
    spec[3].update(fleet=12, outstanding=4, backlog_chunks=[2, 4],
                   pool={"timelines": 2, "interictal_chunks": 6})
    return spec


def _serve(monkeypatch, fault):
    from repro.serving import api

    real = api._jit_engine_step_megabatch
    if fault is not None:
        monkeypatch.setattr(api, "_jit_engine_step_megabatch",
                            lambda *a, **k: fault(real, *a, **k))
    spec = _serve_spec()
    return cell.run(ROOT, "serve.reconnect", SEED, 2.0, False,
                    jax.devices()[:1], time.perf_counter(), spec=spec)


def unchanged_state(real, state, *args, **kw):
    kept = jax.tree.map(jnp.copy, state)    # the step donates its input
    _, votes, frac, alarm, preds = real(state, *args, **kw)
    return (kept, votes, frac, alarm, preds)


def half_batch(real, state, chunks, *args, **kw):
    return real(state, chunks.at[: chunks.shape[0] // 2].set(0.0), *args, **kw)


def altered_answer(real, *args, **kw):
    state, votes, frac, alarm, preds = real(*args, **kw)
    return state, votes, frac, alarm, preds.at[0, 0, 0].set(1 - preds[0, 0, 0])


def test_serving_control_is_caught():
    spec = _serve_spec()
    res = cell.run(ROOT, "serve.reconnect", SEED, 2.0, False,
                   jax.devices()[:1], time.perf_counter(), spec=spec,
                   control=True)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("fault", [unchanged_state, half_batch, altered_answer],
                         ids=lambda f: f.__name__)
def test_serving_fault_is_caught(monkeypatch, fault):
    res = _serve(monkeypatch, fault)
    assert res["correct"] is False, (fault.__name__, res["checks"])


TRAIN_CHILD = r"""
import json, pathlib, sys, time
root = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(root / "src"), str(root / "benchmarks" / "chip")]
import jax, jax.numpy as jnp
from repro.core import forest_trainer
from repro.signal import pipeline
from chipbench import cell, rehearsal

real_fit = forest_trainer.fit_mapreduce
real_moments = forest_trainer.global_moments


def untrained(*a, **k):
    res = real_fit(*a, **k)
    trees = res.forest.trees
    return res._replace(forest=res.forest._replace(trees=trees._replace(
        split_feature=jnp.full_like(trees.split_feature, -1),
        leaf_probs=jnp.zeros_like(trees.leaf_probs).at[..., 0].set(1.0))))


def half_rows(key, x, y, cfg, *, mesh=None, n_shards=None, **k):
    s = mesh.shape["data"]
    def halve(t):
        t = t.reshape((s, -1) + t.shape[1:])
        h = t.shape[1] // 2
        return jnp.concatenate([t[:, :h], t[:, :t.shape[1] - h]], 1).reshape(
            (-1,) + t.shape[2:])
    return real_fit(key, halve(x), halve(y), cfg, mesh=mesh, **k)


def local_moments(feats, combine):
    mean = jnp.mean(feats, axis=0)
    return mean, jnp.sqrt(jnp.mean((feats - mean) ** 2, axis=0)) + 1e-6


def altered(*a, **k):
    res = real_fit(*a, **k)
    return res._replace(feat_mean=res.feat_mean.at[0].add(res.feat_std[0]))


cases = {"sound": {}, "control": {}, "untrained": {"fit_mapreduce": untrained},
         "half_rows": {"fit_mapreduce": half_rows},
         "no_exchange": {"global_moments": local_moments},
         "altered": {"fit_mapreduce": altered}}
spec = rehearsal.tiny_spec(root, "train.mapreduce4")
for name, patch in cases.items():
    forest_trainer.fit_mapreduce = patch.get("fit_mapreduce", real_fit)
    forest_trainer.global_moments = patch.get("global_moments", real_moments)
    res = cell.run(root, "train.mapreduce4", int(sys.argv[2]), 0.5, False,
                   jax.devices()[:4], time.perf_counter(), spec=spec,
                   control=name == "control")
    print(json.dumps({"case": name, "correct": res["correct"],
                      "metrics": sorted(res["metrics"]),
                      "count": res["device"]["count"],
                      "checks": res["checks"]}), flush=True)
"""


def test_training_faults_are_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", TRAIN_CHILD, str(ROOT), str(SEED)], env=env,
        capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = {r["case"]: r for r in map(json.loads, out.stdout.splitlines())}
    assert rows["sound"]["correct"] is True, rows["sound"]
    assert rows["sound"]["metrics"] == ["setup_s", "train_windows_per_s"]
    assert rows["sound"]["count"] == 4
    for case in ("control", "untrained", "half_rows", "no_exchange",
                 "altered"):
        assert rows[case]["correct"] is False, rows[case]
