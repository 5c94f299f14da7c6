"""Partition-spec rules validated against every architecture on an abstract
16x16 (and 2x16x16) mesh — no devices needed."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.dist.partition import batch_specs, cache_specs, param_specs
from repro.models import transformer as T


def _amesh(sizes, names):
    try:                                  # jax >= 0.5 signature
        return AbstractMesh(sizes, names)
    except TypeError:                     # jax 0.4.x: tuple of (name, size)
        return AbstractMesh(tuple(zip(names, sizes)))


MESH = _amesh((16, 16), ("data", "model"))
MESH3 = _amesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisibility(sds_tree, spec_tree, mesh):
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    flat_s = jax.tree.leaves(sds_tree)
    flat_p = jax.tree.leaves(spec_tree,
                             is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p)
    for sds, spec in zip(flat_s, flat_p):
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= sizes[a]
            assert sds.shape[d] % n == 0, (sds.shape, spec)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    cfg = get_config(arch)
    params = T.init_abstract(cfg)
    specs = param_specs(params, mesh, fsdp=True)
    _check_divisibility(params, specs, mesh)


@pytest.mark.parametrize("arch", ["qwen2-72b", "mixtral-8x7b", "zamba2-7b",
                                  "xlstm-1.3b"])
def test_cache_specs_divisible(arch):
    cfg = get_config(arch)
    # a decode cache of 128 sequences x 32768 positions, never allocated
    caches = jax.eval_shape(functools.partial(T.init_cache, cfg, 128, 32768))
    specs = cache_specs(caches, MESH)
    _check_divisibility(caches, specs, MESH)


def test_tp_sharding_hits_big_matrices():
    """The model axis must actually shard the projections of a big config
    (otherwise a sharded step silently replicates 72B params)."""
    cfg = get_config("qwen2-72b")
    params = T.init_abstract(cfg)
    specs = param_specs(params, MESH, fsdp=True)
    wq_spec = specs["layers"]["attn"]["wq"]["w"]
    assert "model" in jax.tree.leaves(wq_spec, is_leaf=lambda x: x is not None) \
        or "model" in tuple(wq_spec), wq_spec
    assert "data" in tuple(wq_spec)
    # embedding vocab-parallel
    emb = specs["embed"]["table"]
    assert tuple(emb)[0] == "model"


def test_granite_vocab_fallback():
    """49155 doesn't divide 16 — the vocab axis must fall back, not crash."""
    cfg = get_config("granite-moe-1b-a400m")
    params = T.init_abstract(cfg)
    specs = param_specs(params, MESH, fsdp=True)
    emb = tuple(specs["embed"]["table"])
    # replicated (see partition.py: GSPMD gather bug workaround)
    assert "model" not in emb
    _check_divisibility(params, specs, MESH)


def test_batch_specs():
    # a train batch of 256 sequences x 4096 tokens, never allocated
    sds = {k: jax.ShapeDtypeStruct((256, 4096), jnp.int32)
           for k in ("tokens", "labels")}
    specs = batch_specs(sds, MESH, batch_axes=("data",))
    assert tuple(specs["tokens"])[0] == "data"
    specs3 = batch_specs(sds, MESH3, batch_axes=("pod", "data"))
    assert tuple(specs3["tokens"])[0] == ("pod", "data")
