"""Hypothesis property tests for the sharding guards: for RANDOM config
dimensions (head counts, expert counts, vocab sizes -- aligned or not),
every produced PartitionSpec must be mesh-valid.  This is the invariant
the mixtral (8 experts on tp=16) and deepseek (56 heads on tp=16) bugs
violated silently before the guards existed.

Settings come from the profile registered in ``tests/conftest.py``
("ci": few derandomized examples on the PR gate; "deep": the nightly
fuzzing job in ci.yml) -- no per-test @settings."""

from __future__ import annotations

import dataclasses

import jax
import pytest

pytest.importorskip("hypothesis")  # property tests need hypothesis; CI installs it
from hypothesis import given, strategies as st
from jax.sharding import AbstractMesh, PartitionSpec as P


from repro.configs import get_config
from repro.models import build
from repro.sharding import rules

MESH = AbstractMesh((16, 16), ("data", "model"))


def _assert_valid(shapes, specs):
    for sds, spec in zip(
            jax.tree.leaves(shapes),
            jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))):
        used = set()
        for dim, entry in zip(sds.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for ax in axes:
                assert ax not in used, (spec, sds.shape)
                used.add(ax)
                total *= MESH.shape[ax]
            assert dim % total == 0, (sds.shape, spec)


@given(
    heads=st.integers(1, 64),
    kv_div=st.integers(1, 8),
    d_mult=st.integers(1, 8),
    strategy=st.sampled_from(["2d", "fsdp", "dp", "dp_vocab"]),
)
def test_dense_param_specs_always_valid(heads, kv_div, d_mult, strategy):
    kv = max(1, heads // kv_div)
    if heads % kv:
        kv = 1
    cfg = dataclasses.replace(
        get_config("qwen3-0.6b").reduced(),
        n_heads=heads, n_kv_heads=kv, head_dim=16,
        d_model=64 * d_mult, d_ff=48 * d_mult,
        vocab_size=100 + d_mult)
    shapes = build(cfg).param_shapes()
    _assert_valid(shapes, rules.param_pspecs(cfg, MESH, shapes, strategy))


@given(
    experts=st.integers(2, 64),
    topk=st.integers(1, 4),
    d_ff=st.sampled_from([48, 64, 256, 768]),
)
def test_moe_param_specs_always_valid(experts, topk, d_ff):
    cfg = dataclasses.replace(
        get_config("qwen3-moe-30b-a3b").reduced(),
        n_experts=experts, experts_per_token=min(topk, experts), d_ff=d_ff)
    shapes = build(cfg).param_shapes()
    _assert_valid(shapes, rules.param_pspecs(cfg, MESH, shapes))


@given(batch=st.integers(1, 512), seq=st.sampled_from([64, 4096, 32768]))
def test_cache_specs_always_valid(batch, seq):
    cfg = get_config("qwen3-0.6b")
    cache = build(cfg).cache_shapes(batch, seq)
    _assert_valid(cache, rules.cache_pspecs(cfg, MESH, cache, batch))
