#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, the control's and
the planted faults', on the chip, several seeds in one process.

  python3 benchmarks/chip/control.py readings --workload serve.reconnect \
      --seeds 1 2 3 --seconds 5
  python3 benchmarks/chip/control.py control --workload serve.reconnect \
      --seeds 1 2 3 --seconds 5
  python3 benchmarks/chip/control.py faults --workload train.mapreduce4 \
      --seeds 1 2 3

``readings`` runs the cell as ``run.py`` does (set-up, a window of
``--seconds``, the comparison) once per seed and prints the compared
numbers and ``correct``. ``control`` runs the cell the same way but
hands the comparison the control's answers -- the reference computed in
bfloat16 (``reference.CONTROL``) put in the program's place -- and prints
the harness's own ``correct``, which has to come out false. ``faults``
(training cells) reads, without a window, the control and the planted
faults against the float32 reference's fit: a fit that leaves the
forest as it started (no split, every leaf on the first class), half of
each shard's rows left out, the exchange of moments between shards left
out (each shard normalizes with its own), and an answer altered where
it is produced (one feature's mean moved by one deviation);
``--answers`` picks some of them. Benchmark runs never run this file.
One JSON object per seed and mode on stdout.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def _local_moments_fit(key, feats, labels, cfg, num):
    """The MapReduce fit with the exchange of moments left out: every
    shard normalizes with its own moments; shard 0's are returned."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    n = feats.shape[0] // cfg.shards
    trees, first = [], None
    for s in range(cfg.shards):
        fs, ys = feats[s * n:(s + 1) * n], labels[s * n:(s + 1) * n]
        mean, std = reference.moments(fs)
        first = first or (mean, std)
        xs = reference.normalize(fs, mean, std)
        keys = jax.random.split(jax.random.fold_in(key, s), cfg.trees_per_shard)
        trees += [reference._fit_tree(k, xs, ys, cfg, num) for k in keys]
    forest = reference.Forest(*(jnp.stack(p) for p in zip(*trees)))
    return forest, first[0], first[1]


def _half_rows(feats, shards: int):
    """Each shard's second half of rows replaced by its first half."""
    import jax.numpy as jnp

    n = feats.shape[0] // shards
    parts = []
    for s in range(shards):
        head = feats[s * n:s * n + n // 2]
        parts += [head, head[: n - n // 2]]
    return jnp.concatenate(parts)


TRAIN_ANSWERS = ("untrained", "control", "half_rows", "no_exchange", "altered")


def train_control(spec, seed: int, devices, names=TRAIN_ANSWERS) -> dict:
    import jax
    import numpy as np

    from chipbench import eeg, reference, train

    _, _, cfg, traffic = spec
    sig = eeg.Signal.of(cfg)
    k_fit, data = train.make_data(cfg, seed, devices[0])
    job = train.Job(None, data, k_fit, list(devices), cfg, traffic)
    fcfg = train.fit_config(job)
    fit = jax.jit(_local_moments_fit, static_argnames=("cfg", "num"))
    out: dict = {}
    for i in range(traffic["check_fits"]):
        p = i % len(data)
        key = jax.random.fold_in(k_fit, i)
        held = train.heldout(job, i, p)
        w, y = data[p]
        feats = reference.features_in_blocks(sig.as_chunks(w))
        feats = feats.reshape(-1, feats.shape[-1])
        truth = reference.fit(key, feats, y, fcfg)
        forest, mean, std = truth
        answers = {
            "untrained": lambda: (reference.Forest(
                forest.rotation, forest.feature * 0 - 1,
                forest.threshold * 0 + np.inf,
                forest.leaf * 0 + np.asarray([1.0, 0.0], np.float32)),
                mean, std),
            "control": lambda: reference.fit(
                key, reference.features_in_blocks(
                    sig.as_chunks(w), reference.CONTROL
                ).reshape(feats.shape), y, fcfg, reference.CONTROL),
            "half_rows": lambda: reference.fit(
                key, _half_rows(feats, fcfg.shards),
                _half_rows(y, fcfg.shards), fcfg),
            "no_exchange": lambda: fit(key, feats, y, fcfg, reference.EXACT),
            "altered": lambda: (forest, mean.at[0].add(std[0]), std),
        }
        for name in names:
            g, b, n = train.compare(answers[name](), truth, held)
            r = out.setdefault(name, {"moment_gap": 0.0, "bad": 0, "n": 0})
            r["moment_gap"] = max(r["moment_gap"], g)
            r["bad"] += b
            r["n"] += n
    return {name: {"moment_gap": r["moment_gap"],
                   "heldout_disagree": r["bad"] / r["n"]}
            for name, r in out.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "control", "faults"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--answers", nargs="+", choices=TRAIN_ANSWERS,
                    default=TRAIN_ANSWERS,
                    help="training control: which answers to read")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))

    import jax

    from chipbench import cell

    cell.use_cache(ROOT)
    spec = cell.load_spec(ROOT, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("control: JAX finds no TPU")
    kind = spec[2]["kind"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        if args.mode in ("readings", "control"):
            res = cell.run(ROOT, args.workload, seed, args.seconds, False,
                           devices[:spec[1]["chips"]], time.perf_counter(),
                           spec=spec, control=args.mode == "control")
            numbers = {k: c["value"] for k, c in res["checks"].items()}
            numbers["correct"] = res["correct"]
        elif kind == "train":
            # Read on the first chip, with the cell's map shards.
            numbers = train_control(spec, seed,
                                    [devices[0]] * spec[1]["chips"],
                                    args.answers)
        else:
            sys.exit("control: faults are read for training cells only")
        print(json.dumps({"mode": args.mode, "workload": args.workload,
                          "seed": seed, "numbers": numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)


if __name__ == "__main__":
    main()
