"""MapReduce as a composable JAX module.

This is the paper's systems contribution (Sec. 2.4) adapted from Hadoop to
TPU SPMD. The correspondence (DESIGN.md Sec. 2):

  * input splits  -> a global array sharded along its leading axis over a
                     named mesh axis (``data`` by default);
  * map task      -> a per-shard function run inside ``shard_map``;
  * shuffle       -> ``lax.all_to_all`` keyed exchange (optional);
  * reduce task   -> a jax collective (``psum`` / ``all_gather`` / custom
                     monoid) across the same axis.

Two execution modes share one API:

  * ``run(mesh, ...)``      -- real SPMD via ``shard_map`` (the production
                               path; also what the dry-run lowers).
  * ``run_local(n_shards)`` -- ``vmap`` emulation on a single device (what
                               unit tests and the CPU container use; it is
                               bit-identical for deterministic map fns).

The reduce combiner must be a *commutative monoid* (the same requirement
Hadoop places on combiners); we provide the common ones.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

MapFn = Callable[..., Any]  # (shard_data...) -> mapped pytree
ReduceFn = Callable[[Any, str], Any]  # (mapped, axis_name) -> reduced pytree


# ---------------------------------------------------------------------------
# Standard reducers (commutative monoids over a named axis)
# ---------------------------------------------------------------------------

def reduce_sum(mapped, axis_name: str):
    return jax.tree.map(lambda t: jax.lax.psum(t, axis_name), mapped)


def reduce_mean(mapped, axis_name: str):
    return jax.tree.map(lambda t: jax.lax.pmean(t, axis_name), mapped)


def reduce_max(mapped, axis_name: str):
    return jax.tree.map(lambda t: jax.lax.pmax(t, axis_name), mapped)


def reduce_concat(mapped, axis_name: str):
    """Union reduce: all_gather shards and flatten the shard axis into the
    leading axis. This is the forest-union reduce of the paper (each map
    task trains a sub-forest; the ensemble is the concatenation)."""

    def cat(t):
        g = jax.lax.all_gather(t, axis_name)  # (n_shards, ...) identical on all
        return g.reshape((-1,) + g.shape[2:]) if g.ndim >= 2 else g.reshape(-1)

    return jax.tree.map(cat, mapped)


# ---------------------------------------------------------------------------
# The MapReduce job
# ---------------------------------------------------------------------------

class MapReduce:
    """A Hadoop-style job expressed as shard_map(map) + collective(reduce).

    map_fn     : per-shard function. Receives each input pytree with its
                 leading axis divided by the number of shards.
    reduce_fn  : one of the reducers above (or any (mapped, axis) -> pytree).
    axis_name  : mesh axis carrying the input splits.
    """

    def __init__(
        self,
        map_fn: MapFn,
        reduce_fn: ReduceFn = reduce_concat,
        axis_name: str = "data",
    ):
        self.map_fn = map_fn
        self.reduce_fn = reduce_fn
        self.axis_name = axis_name

    # -- production path ----------------------------------------------------
    def run(self, mesh: Mesh, *inputs, replicated_inputs: tuple = ()):
        """Execute on ``mesh``: inputs sharded on their leading axis along
        ``self.axis_name``; ``replicated_inputs`` broadcast to every shard.
        Returns the reduced pytree (replicated)."""
        axis = self.axis_name
        in_specs = tuple(P(axis) for _ in inputs) + tuple(
            P() for _ in replicated_inputs
        )

        def job(*args):
            mapped = self.map_fn(*args)
            return self.reduce_fn(mapped, axis)

        fn = jax.shard_map(
            job, mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False,
        )
        return fn(*inputs, *replicated_inputs)

    # -- single-device emulation --------------------------------------------
    def run_local(self, n_shards: int, *inputs, replicated_inputs: tuple = ()):
        """vmap emulation: split leading axes into ``n_shards``, vmap the
        map fn, apply the reduce monoid with jnp ops. Semantically equal to
        ``run`` for deterministic map fns. The vmap carries
        ``self.axis_name``, so map fns may use collectives (``psum``,
        ``all_gather``, ...) over it exactly as they would inside
        ``shard_map``."""

        def split(t):
            return t.reshape((n_shards, t.shape[0] // n_shards) + t.shape[1:])

        shards = tuple(jax.tree.map(split, t) for t in inputs)
        mapped = jax.vmap(
            lambda *xs: self.map_fn(*xs, *replicated_inputs),
            axis_name=self.axis_name,
        )(*shards)
        return _local_reduce(self.reduce_fn, mapped)


def _local_reduce(reduce_fn: ReduceFn, mapped):
    """Interpret the standard reducers over a materialized shard axis."""
    if reduce_fn is reduce_sum:
        return jax.tree.map(lambda t: jnp.sum(t, axis=0), mapped)
    if reduce_fn is reduce_mean:
        return jax.tree.map(lambda t: jnp.mean(t, axis=0), mapped)
    if reduce_fn is reduce_max:
        return jax.tree.map(lambda t: jnp.max(t, axis=0), mapped)
    if reduce_fn is reduce_concat:
        return jax.tree.map(
            lambda t: t.reshape((-1,) + t.shape[2:]) if t.ndim >= 2 else t.reshape(-1),
            mapped,
        )
    raise ValueError(
        "run_local only supports the built-in reducers; use run() on a mesh "
        "for custom reduce fns."
    )


# ---------------------------------------------------------------------------
# Keyed shuffle (the Hadoop sort/shuffle stage)
# ---------------------------------------------------------------------------

def shuffle_by_key(values: jax.Array, keys: jax.Array, axis_name: str, n_shards: int):
    """Inside shard_map: redistribute rows so that row i lands on shard
    ``keys[i] % n_shards``. Static-shaped all_to_all: each shard sends an
    equal-sized bucket of ``rows_per_shard // n_shards`` rows to every
    other shard.

    Headroom contract (enforced): ``rows_per_shard % n_shards == 0`` --
    a ragged row count cannot fill equal buckets and is rejected rather
    than silently truncated. Even with the contract satisfied, key skew
    can overflow a destination: a shard keying MORE than ``bucket`` rows
    to one destination keeps the first ``bucket`` of them (stable local
    order) and DROPS the excess; destinations receiving fewer are
    zero-padded. Callers pick ``rows_per_shard`` with headroom for their
    worst-case skew (Hadoop's fixed-size spill buckets have the same
    failure mode). The pre-guard implementation packed the sorted rows
    into buckets regardless of destination boundaries, silently
    MISROUTING every overflow row into the next shard's bucket.
    """
    rows_per_shard = values.shape[0]
    if rows_per_shard % n_shards != 0:
        raise ValueError(
            f"shuffle_by_key: rows_per_shard={rows_per_shard} not divisible "
            f"by n_shards={n_shards}; equal send buckets would drop the "
            f"{rows_per_shard % n_shards} trailing rows silently. Pad rows "
            "upstream to a multiple of n_shards."
        )
    bucket = rows_per_shard // n_shards
    dest = keys % n_shards
    order = jnp.argsort(dest)  # stable: preserves local row order per dest
    sorted_dest = dest[order]
    values_sorted = values[order]
    # Rank of each row within its destination group; rows past the
    # bucket capacity scatter out of bounds and are dropped.
    group_start = jnp.searchsorted(sorted_dest, jnp.arange(n_shards))
    pos = jnp.arange(rows_per_shard) - group_start[sorted_dest]
    slot = jnp.where(
        pos < bucket, sorted_dest * bucket + pos, rows_per_shard
    )
    send = jnp.zeros_like(values)
    send = send.at[slot].set(values_sorted, mode="drop")
    # (n_shards, bucket, ...) send buckets; all_to_all swaps the leading axis.
    send = send.reshape((n_shards, bucket) + values.shape[1:])
    recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=False)
    return recv.reshape((n_shards * bucket,) + values.shape[1:])


__all__ = [
    "MapReduce",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_concat",
    "shuffle_by_key",
]
