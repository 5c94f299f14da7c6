"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) never checks what the chip's
compiler refuses: block tiling, VMEM budget, unsupported primitives and
casts.  These tests lower each kernel at real widths with Mosaic against
a ``v5e:2x2`` topology description — no chip is needed and nothing runs.

The topology is described inside the module fixture only (never at
import), so every pytest-xdist worker collects the same tests and only
the worker running this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.envs import make_env
from repro.kernels import ops

N_ENVS, T, SLOTS = 16384, 16, 2          # Ant rollout ring of the smoke run


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    prev_cache = jax.config.jax_enable_compilation_cache
    # an AOT compile for a described chip is written to the persistent
    # cache but can never be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler plugin installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ant_ring(s, keys=("obs", "actions", "rewards", "dones")):
    f32 = jnp.float32
    shapes = {"obs": (T, SLOTS * N_ENVS, 60), "actions": (T, SLOTS * N_ENVS, 8),
              "rewards": (T, SLOTS * N_ENVS), "dones": (T, SLOTS * N_ENVS),
              "bootstrap": (SLOTS, N_ENVS), "actor_version": (SLOTS, 1)}
    return {k: _sds(shapes[k], jnp.int32 if k == "actor_version" else f32, s)
            for k in keys}


def _env_mega_step(s):
    env = make_env("Ant", megakernel=True)
    mc, spec = env.mega, env.spec
    N, J, O, f32, i32 = N_ENVS, spec.act_dim, spec.obs_dim, jnp.float32, \
        jnp.int32
    state = [_sds((N, J), f32, s), _sds((N, J), f32, s),
             _sds((N, 6), f32, s), _sds((N, J), f32, s),
             _sds((N,), i32, s), _sds((N,), i32, s), _sds((N,), i32, s)]
    consts = [_sds(x.shape, x.dtype, s)
              for x in (mc.sensor, mc.tgt, mc.masses, mc.lengths)]
    return ops.env_mega_step.lower(
        *state, _sds((N, J), f32, s), _sds((N, O), f32, s), _ant_ring(s),
        _sds((), i32, s), _sds((), i32, s), *consts, chain=mc.chain,
        task=mc.task, substeps=spec.substeps, dt=spec.dt,
        max_episode_len=spec.max_episode_len, interpret=False)


def _pack_channels(s):
    N, f32 = N_ENVS, jnp.float32
    pay = {"obs": _sds((T, N, 60), f32, s), "actions": _sds((T, N, 8), f32, s),
           "rewards": _sds((T, N), f32, s), "dones": _sds((T, N), f32, s),
           "bootstrap": _sds((N,), f32, s),
           "actor_version": _sds((), jnp.int32, s)}
    rings = _ant_ring(s, keys=("obs", "actions", "rewards", "dones",
                               "bootstrap", "actor_version"))
    return ops.pack_channels.lower(rings, pay, _sds((), jnp.int32, s),
                                   interpret=False)


def _gae_norm(s, N=65536):
    x = _sds((T, N), jnp.float32, s)
    return ops.gae_norm.lower(x, x, x, _sds((N,), jnp.float32, s),
                              interpret=False)


def _nstep_returns(s, N=65536):
    x = _sds((T, N), jnp.float32, s)
    return ops.nstep_returns.lower(x, x, _sds((N,), jnp.float32, s),
                                   interpret=False)


def _paged_attention(s):
    # internlm2-1.8b attention: 16 query heads, 8 KV heads of width 128
    B, H, KH, hd, page, pages, M = 4, 16, 8, 128, 16, 257, 64
    bf16, i32 = jnp.bfloat16, jnp.int32
    kv = _sds((pages, page, KH, hd), bf16, s)
    return ops.paged_attention.lower(
        _sds((B, H, hd), bf16, s), kv, kv, _sds((pages, page), i32, s),
        _sds((B, M), i32, s), _sds((B,), i32, s), interpret=False)


@pytest.mark.parametrize("lower", [_env_mega_step, _pack_channels, _gae_norm,
                                   _nstep_returns, _paged_attention],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, lower):
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
