"""Training driver.

On the CPU container this runs REDUCED configs end-to-end (the full
configs are exercised by launch/dryrun.py); on a real TPU slice the same
driver runs the full config with the production mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced \
      --steps 20 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-1.3b --reduced \
      --steps 10 --ensemble 4          # paper's MapReduce ensemble schedule
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import checkpoint as ckpt
from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import InputShape
from repro.data.synthetic import make_batch
from repro.launch.mesh import make_data_mesh
from repro.models import build
from repro.optim import AdamWConfig, adamw, cosine_warmup
from repro.training import TrainState, make_train_step
from repro.training.trainer import ensemble_init, make_ensemble_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--ensemble", type=int, default=0,
                    help="train N bagged members (paper schedule T1)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    print(f"[train] {cfg.name}: {model.param_count():,} params, "
          f"{cfg.n_layers}L d={cfg.d_model}")

    opt = adamw(AdamWConfig(lr=args.lr),
                cosine_warmup(args.lr, max(args.steps // 10, 1), args.steps))
    rng = jax.random.PRNGKey(args.seed)
    shape = InputShape("cli", args.seq, args.batch, "train")

    if args.ensemble:
        mesh = make_data_mesh(1)
        state = ensemble_init(model, opt, rng, args.ensemble)
        step = jax.jit(make_ensemble_train_step(model, opt, mesh,
                                                args.ensemble))
    else:
        state = TrainState(model.init(rng), opt.init(model.init(rng)))
        step = jax.jit(make_train_step(
            model, opt, microbatches=args.microbatches or None))

    t0 = time.time()
    for i in range(args.steps):
        batch = make_batch(cfg, shape, seed=args.seed + i + 1)
        state, metrics = step(state, batch)
        loss = np.asarray(metrics["loss"])
        loss_s = (f"{float(loss):.4f}" if loss.ndim == 0
                  else "[" + " ".join(f"{x:.3f}" for x in loss) + "]")
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            print(f"[train] step {i:4d} loss={loss_s} "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
        if args.ckpt_dir and args.ckpt_every and (i + 1) % args.ckpt_every == 0:
            path = ckpt.save(args.ckpt_dir, i + 1, state)
            print(f"[train] checkpoint -> {path}")
    assert np.all(np.isfinite(np.asarray(metrics["loss"]))), "NaN loss"
    print("[train] done")


if __name__ == "__main__":
    main()
