"""The paper's MapReduce ensemble schedule GENERALIZED to the model zoo
(DESIGN.md T1): train N bagged members of an assigned architecture on
disjoint data shards with NO gradient sync, then vote-reduce their
predictions -- exactly the Rotation-Forest-over-Hadoop layout, with
transformer/SSM members instead of trees.

  PYTHONPATH=src python examples/ensemble_lm.py --arch xlstm-1.3b \
      --members 4 --steps 15
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.configs.base import InputShape
from repro.data.synthetic import make_batch
from repro.launch.mesh import make_data_mesh
from repro.models import build
from repro.optim import AdamWConfig, adamw
from repro.training.trainer import (ensemble_init, make_ensemble_train_step)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_NAMES)
    ap.add_argument("--members", type=int, default=4)
    ap.add_argument("--steps", type=int, default=15)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    model = build(cfg)
    opt = adamw(AdamWConfig(lr=1e-3))
    mesh = make_data_mesh(1)
    print(f"[ensemble] {args.members} x {cfg.name} "
          f"({model.param_count():,} params each)")

    states = ensemble_init(model, opt, jax.random.PRNGKey(0), args.members)
    step = jax.jit(make_ensemble_train_step(model, opt, mesh, args.members))
    shape = InputShape("ens", 64, 4 * args.members, "train")

    for i in range(args.steps):
        batch = make_batch(cfg, shape, seed=i)
        states, metrics = step(states, batch)
        losses = " ".join(f"{x:.3f}" for x in jnp.asarray(metrics['loss']))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"[ensemble] step {i}: member losses [{losses}]")

    # --- vote-reduce (the paper's reduce phase) ---------------------------
    eval_batch = make_batch(cfg, InputShape("eval", 64, 2, "train"), seed=99)
    member_logits = jax.vmap(
        lambda p: model.forward(p, eval_batch)[0])(states.params)
    vote_probs = jnp.mean(jax.nn.softmax(member_logits, -1), axis=0)
    vote_nll = -jnp.mean(jnp.log(jnp.take_along_axis(
        vote_probs, eval_batch["targets"][..., None], -1) + 1e-9))
    single_probs = jax.nn.softmax(member_logits[0], -1)
    single_nll = -jnp.mean(jnp.log(jnp.take_along_axis(
        single_probs, eval_batch["targets"][..., None], -1) + 1e-9))
    print(f"[ensemble] held-out NLL: single member {float(single_nll):.4f} "
          f"vs {args.members}-member vote {float(vote_nll):.4f} "
          "(ensemble <= single, the paper's claim)")


if __name__ == "__main__":
    main()
