"""Training cells: back-to-back MapReduce fits of per-patient training
sets, one map shard per chip.

Set-up makes every patient's training set on the device from the seed
(whole 60-window chunks, classes stratified so that every shard holds
both) and shards it along the mesh's ``data`` axis, then runs one fit to
compile. The window fits patient ``i mod patients`` with key
``fold_in(key, i)`` through ``pipeline.fit(mesh=)``, each fit ending in
``block_until_ready``, until ``seconds`` have passed; the rate covers
every fit and all the time from the first to the end of the last.

The configuration's ``fit`` says how the program fits: ``{"mesh": true}``
(the default) runs ``pipeline.fit(mesh=)`` with one map shard per chip
of the cell; ``{"mesh": false, "shards": n}`` runs the ``n_shards=n``
emulation on the first chip. The traffic (``traffic/<mix>.json``) gives
``check_fits`` (how many of the window's first fits the reference
follows) and ``heldout_interictal_chunks`` (the held-out stream each
checked fit is scored on: that many interictal chunks, then the
preictal run-up).
"""

from __future__ import annotations

import time
from typing import NamedTuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import eeg, program, reference, spans


class Job(NamedTuple):
    fit: object                    # jitted (key, windows, labels) -> fitted
    data: list                     # [(windows, labels)] sharded per patient
    key: jax.Array
    devices: list
    cfg: dict
    traffic: dict


def setup(cfg: dict, traffic: dict, seed: int, devices: list, log) -> Job:
    from repro.launch.mesh import make_data_mesh
    from repro.signal import pipeline
    from repro.signal.eeg_data import Recording

    pcfg = program.pipeline_config(cfg)
    if fit_options(cfg, devices)["mesh"]:
        mesh = make_data_mesh(len(devices))
        k_fit, data = make_data(cfg, seed, NamedSharding(mesh, P("data")))
        fit = jax.jit(lambda k, w, y: pipeline.fit(k, Recording(w, y), pcfg,
                                                   mesh=mesh))
    else:
        shards = fit_options(cfg, devices)["shards"]
        k_fit, data = make_data(cfg, seed, devices[0])
        fit = jax.jit(lambda k, w, y: pipeline.fit(k, Recording(w, y), pcfg,
                                                   n_shards=shards))
    log(f"data: {len(data)} patients x {data[0][0].shape[0]} windows, "
        f"{sum(w.nbytes for w, _ in data)} bytes over {len(devices)} chips")

    jax.block_until_ready(fit(jax.random.fold_in(k_fit, 2**31 - 1), *data[0]))
    log("warm-up: one fit compiled and run")
    return Job(fit, data, k_fit, devices, cfg, traffic)


def fit_options(cfg: dict, devices: list) -> dict:
    """{"mesh": bool, "shards": map shards} of the configuration's fit."""
    opts = dict(cfg.get("fit", {}))
    mesh = opts.get("mesh", True)
    return {"mesh": mesh,
            "shards": len(devices) if mesh else opts.get("shards", 1)}


def make_data(cfg: dict, seed: int, sharding) -> tuple[jax.Array, list]:
    """(fit key, [(windows, labels)] per patient placed by ``sharding``)."""
    t = cfg["training_set"]
    k_data, k_fit = jax.random.split(eeg.key_from_seed(seed))
    data = []
    for p in range(cfg["patients"]):
        w, y = eeg.training_set(jax.random.fold_in(k_data, p), p,
                                n_inter=t["interictal_windows"],
                                n_pre=t["preictal_windows"])
        data.append(jax.device_put((w, y), sharding))
    return k_fit, jax.block_until_ready(data)


class Window(NamedTuple):
    seconds: float
    fits: int
    windows: int                   # training windows over all fits
    kept: list                     # [(i, patient, key, fitted)] to check


def run_window(job: Job, seconds: float, seed: int, rec: spans.Recorder
               ) -> Window:
    n_keep = job.traffic["check_fits"]
    kept = []
    i = 0
    t0 = time.perf_counter()
    with rec.window():
        while True:
            p = i % len(job.data)
            k = jax.random.fold_in(job.key, i)
            with rec.span("bench.fit"):
                fitted = jax.block_until_ready(job.fit(k, *job.data[p]))
            if i < n_keep:
                kept.append((i, p, k, fitted))
            i += 1
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
    rows = job.data[0][0].shape[0]
    rec.count("fits", i)
    rec.count("windows_trained", i * rows)
    return Window(t - t0, i, i * rows, kept)


def end_to_end(w: Window) -> dict:
    return {"train_windows_per_s": w.windows / w.seconds}


def attempted_failed(w: Window) -> tuple[int, int]:
    return w.fits, 0


def _as_forest(fitted) -> tuple[reference.Forest, jax.Array, jax.Array]:
    """The program's fitted pipeline in the reference's plain form (its
    answer, read, not used to make the reference's own)."""
    f = jax.device_get(fitted)
    trees = f.forest.trees
    feat = np.asarray(trees.split_feature)
    sbin = np.asarray(trees.split_bin)
    edges = np.asarray(trees.bin_edges)
    n_edges = edges.shape[-1]
    live = (feat >= 0) & (sbin < n_edges)
    t_idx = np.arange(feat.shape[0])[:, None]
    thr = edges[t_idx, np.maximum(feat, 0), np.minimum(sbin, n_edges - 1)]
    forest = reference.Forest(np.asarray(f.forest.rotation), feat,
                              np.where(live, thr, np.inf).astype(np.float32),
                              np.asarray(trees.leaf_probs))
    return forest, np.asarray(f.feat_mean), np.asarray(f.feat_std)


def reference_fit(job: Job, patient: int, key):
    """The reference's own answer for one fit: features of the patient's
    whole training set, then the MapReduce fit, on the first chip."""
    w, y = jax.device_put(job.data[patient], job.devices[0])
    feats = reference.features_in_blocks(
        w.reshape(-1, eeg.CHUNK, eeg.N_CHANNELS, eeg.WINDOW))
    return reference.fit(key, feats.reshape(-1, feats.shape[-1]), y,
                         fit_config(job))


def fit_config(job: Job) -> reference.FitConfig:
    fc = job.cfg["pipeline"]["forest"]
    shards = fit_options(job.cfg, job.devices)["shards"]
    return reference.FitConfig(
        shards=shards, trees_per_shard=-(-fc["n_trees"] // shards),
        subsets=fc["n_subsets"], depth=fc["depth"], bins=fc["n_bins"],
        classes=fc["n_classes"])


def heldout(job: Job, i: int, patient: int):
    """Reference features (device 0) of a held-out stream of the patient."""
    key = jax.random.fold_in(jax.random.fold_in(job.key, 2**30), i)
    chunks, _ = eeg.timeline(
        key, patient,
        n_inter_chunks=job.traffic["heldout_interictal_chunks"])
    feats = reference.features_in_blocks(
        jax.device_put(chunks, job.devices[0]))
    return feats.reshape(-1, feats.shape[-1])


def compare(answer, truth, held) -> tuple[float, float, int]:
    """(moment gap, held-out disagreements, held-out windows) of one fit's
    answer against the reference's: the worst gap of a feature's mean or
    standard deviation in units of the reference's deviation, and the
    windows of a held-out stream the two forests label differently, each
    forest scoring with its own moments."""
    (fa, ma, sa), (ft, mt, st) = answer, truth
    mt, st = np.asarray(mt), np.asarray(st)
    gap = float(max(np.max(np.abs(ma - mt) / st),
                    np.max(np.abs(sa - st) / st)))
    if np.shape(fa.rotation) != np.shape(ft.rotation):
        return float("inf"), held.shape[0], held.shape[0]
    la = np.asarray(reference.predict(fa, reference.normalize(held, ma, sa)))
    lt = np.asarray(reference.predict(ft, reference.normalize(held, mt, st)))
    return gap, int(np.sum(la != lt)), int(la.size)


def control_fit(job: Job, patient: int, key):
    """The control's answer for one fit: the reference computed in
    bfloat16, put in the program's place."""
    w, y = jax.device_put(job.data[patient], job.devices[0])
    feats = reference.features_in_blocks(
        w.reshape(-1, eeg.CHUNK, eeg.N_CHANNELS, eeg.WINDOW),
        reference.CONTROL)
    return reference.fit(key, feats.reshape(-1, feats.shape[-1]), y,
                         fit_config(job), reference.CONTROL)


def check(job: Job, w: Window, seed: int, control: bool = False) -> dict:
    """The window's first fits against the reference's own; with
    ``control`` the answers are the control's, not the program's."""
    gaps, bad, total = [], 0, 0
    for i, p, k, fitted in w.kept:
        held = heldout(job, i, p)
        answer = control_fit(job, p, k) if control else _as_forest(fitted)
        g, b, n = compare(answer, reference_fit(job, p, k), held)
        gaps.append(g)
        bad += b
        total += n
    return {
        "moment_gap": max(gaps) if gaps else float("inf"),
        "heldout_disagree": bad / total if total else 1.0,
        "fits_checked": len(gaps),
    }
