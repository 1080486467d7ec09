"""Sharding rules: every spec produced for every (arch x shape x strategy)
must be mesh-valid -- sharded dims divisible by their axis sizes, no axis
used twice in one spec.  Uses an AbstractMesh of the production shape (no
512 host devices needed)."""

from __future__ import annotations

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_NAMES, INPUT_SHAPES, get_config, shape_applicable
from repro.data.synthetic import batch_specs
from repro.models import build, for_shape
from repro.sharding import rules


def _mesh(multi_pod=False):
    if multi_pod:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _check_tree(mesh, shapes, specs):
    leaves_s = jax.tree.leaves(shapes)
    leaves_p = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_s) == len(leaves_p)
    for sds, spec in zip(leaves_s, leaves_p):
        used = []
        assert len(spec) <= len(sds.shape), (sds.shape, spec)
        for dim, entry in zip(sds.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for ax in axes:
                assert ax in mesh.shape, (ax, spec)
                assert ax not in used, f"axis {ax} reused in {spec}"
                used.append(ax)
                total *= mesh.shape[ax]
            assert dim % total == 0, (sds.shape, spec, dim, total)


@pytest.mark.parametrize("arch", ARCH_NAMES)
@pytest.mark.parametrize("multi_pod", [False, True])
@pytest.mark.parametrize("strategy", ["2d", "fsdp", "dp"])
def test_param_specs_valid(arch, multi_pod, strategy):
    mesh = _mesh(multi_pod)
    cfg = get_config(arch)
    model = build(cfg)
    shapes = model.param_shapes()
    specs = rules.param_pspecs(cfg, mesh, shapes, strategy)
    _check_tree(mesh, shapes, specs)


@pytest.mark.parametrize("arch", ARCH_NAMES)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_batch_and_cache_specs_valid(arch, shape_name):
    mesh = _mesh()
    shape = INPUT_SHAPES[shape_name]
    cfg = for_shape(get_config(arch), shape_name)
    if not shape_applicable(cfg, shape)[0]:
        pytest.skip("shape not applicable")
    model = build(cfg)
    batch = batch_specs(cfg, shape)
    _check_tree(mesh, batch, rules.batch_pspecs(cfg, mesh, batch))
    if shape.kind == "decode":
        cache = model.cache_shapes(shape.global_batch, shape.seq_len)
        _check_tree(mesh, cache,
                    rules.cache_pspecs(cfg, mesh, cache, shape.global_batch))


def test_big_kv_cache_actually_sharded():
    """decode_32k GQA cache must shard batch AND (heads or sequence):
    an unsharded 32k cache is ~0.5 TB (the bug this guards against)."""
    mesh = _mesh()
    cfg = get_config("qwen3-0.6b")
    model = build(cfg)
    cache = model.cache_shapes(128, 32768)
    specs = rules.cache_pspecs(cfg, mesh, cache, 128)
    k_spec = tuple(specs["layers"]["k"])
    flat = [a for e in k_spec if e for a in
            (e if isinstance(e, tuple) else (e,))]
    assert "data" in flat and "model" in flat, k_spec
