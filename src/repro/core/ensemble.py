"""Distributed ensembles: the paper's MapReduce training scheme,
generalized so *any* model in the zoo (rotation forest, or a transformer
classification head) can be bagged across the mesh.

The paper trains the Rotation Forest "on each dataset in parallel using a
cluster of computers" -- i.e. ensemble members are embarrassingly parallel
over data shards (map) and combined by vote (reduce). Here:

  * ``DistributedEnsemble``      -- fit_fn/predict_fn pairs (classical ML);
    each mesh shard along ``data`` trains one member on its own data shard,
    predictions are vote-reduced. This is T1 in DESIGN.md Sec. 5.
  * ``ensemble_train_step``      -- the same schedule for gradient models:
    identical to data-parallel SGD *minus the gradient psum*; members
    diverge (bagging), and ``ensemble_predict`` vote-reduces their logits.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import mapreduce as mr


class DistributedEnsemble:
    """Bagged ensemble over the mesh ``data`` axis.

    fit_fn     : (rng, x_shard, y_shard) -> member params pytree
    predict_fn : (member params, x) -> (N, C) class probabilities
    """

    def __init__(
        self,
        fit_fn: Callable[[jax.Array, jax.Array, jax.Array], Any],
        predict_fn: Callable[[Any, jax.Array], jax.Array],
        axis_name: str = "data",
    ):
        self.fit_fn = fit_fn
        self.predict_fn = predict_fn
        self.axis_name = axis_name

    # --- training: map = fit a member per shard; reduce = union ------------
    def fit(self, mesh: Mesh, rng: jax.Array, x: jax.Array, y: jax.Array):
        axis = self.axis_name

        def job(x_s, y_s):
            member = jnp.sum(
                jax.lax.axis_index(axis) if isinstance(axis, str) else 0
            )
            key = jax.random.fold_in(rng, member)
            params = self.fit_fn(key, x_s, y_s)
            # Union-reduce: gather every member's params (leading member axis).
            return mr.reduce_concat(
                jax.tree.map(lambda t: t[None], params), axis
            )

        fn = jax.shard_map(
            job, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P(),
            check_vma=False,
        )
        return fn(x, y)

    def fit_local(self, n_members: int, rng: jax.Array, x: jax.Array, y: jax.Array):
        """Single-device emulation (vmap over members / data shards)."""

        def split(t):
            return t.reshape((n_members, t.shape[0] // n_members) + t.shape[1:])

        keys = jax.random.split(rng, n_members)
        return jax.vmap(self.fit_fn)(keys, split(x), split(y))

    # --- inference: map = member predict; reduce = vote ---------------------
    def predict_proba(self, params: Any, x: jax.Array) -> jax.Array:
        """params has a leading member axis; vote = mean of member probs."""
        probs = jax.vmap(lambda p: self.predict_fn(p, x))(params)
        return jnp.mean(probs, axis=0)

    def predict(self, params: Any, x: jax.Array) -> jax.Array:
        return jnp.argmax(self.predict_proba(params, x), axis=-1)


# ---------------------------------------------------------------------------
# Gradient-model variant (used by training/ for the model zoo)
# ---------------------------------------------------------------------------

def ensemble_grads(loss_fn, params, batch, ensemble_axis: str | None):
    """Per-member gradients: exactly data-parallel grads WITHOUT the psum
    over ``ensemble_axis``. With ``ensemble_axis=None`` this degenerates to
    standard single-model grads (the non-ensemble baseline)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    # NOTE the deliberate absence of jax.lax.pmean(grads, ensemble_axis):
    # members see disjoint data shards and diverge -- that is the bagging.
    return loss, grads


def ensemble_vote(logits: jax.Array, axis_name: str) -> jax.Array:
    """Vote-reduce member logits -> replicated ensemble probabilities."""
    probs = jax.nn.softmax(logits, axis=-1)
    return jax.lax.pmean(probs, axis_name)
