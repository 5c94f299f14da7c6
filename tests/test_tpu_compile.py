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
import re

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


def _gmm(s):
    # one Moonlight MoE layer's held experts (8 of width 1408 at d_model
    # 2048) over a 1-row minibatch's assignment buffer (8192 tokens x 6)
    f32 = jnp.float32
    return jax.jit(lambda x, w, n: ops.gmm(x, w, n, interpret=False)).lower(
        _sds((8192 * 6, 2048), f32, s), _sds((8, 2048, 1408), f32, s),
        _sds((8,), jnp.int32, s))


def _pallas_names(text):
    """Instruction names of the compiled Pallas kernels in ``text``."""
    return re.findall(r"^\s*(?:ROOT )?%(\S+) = .*tpu_custom_call", text,
                      re.MULTILINE)


@pytest.mark.parametrize("lower", [_env_mega_step, _pack_channels, _gae_norm,
                                   _nstep_returns, _paged_attention, _gmm],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, lower):
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # a kernel's instruction is named after its jitted ops wrapper
    names = _pallas_names(compiled.as_text())
    assert names and all(n.startswith(lower.__name__.lstrip("_"))
                         for n in names), names


# the programs in which the benchmark's kernel metrics find their kernel
# by its instruction name (bench/metrics/*.py)
def _collect_ring(s, N=1024):
    from repro.models.policy import init_policy, policy_apply
    from repro.rl import rollout
    env = make_env("Ant", megakernel=True)
    mc, spec = env.mega, env.spec

    def sds(x):
        return _sds(x.shape, x.dtype, s)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda: init_policy(jax.random.key(0), spec.policy_dims)))
    es, obs = jax.tree.map(sds, jax.eval_shape(
        lambda: env.reset(jax.random.PRNGKey(0), N)))
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    ring = {"obs": (T, SLOTS * N, spec.obs_dim),
            "actions": (T, SLOTS * N, spec.act_dim),
            "rewards": (T, SLOTS * N), "dones": (T, SLOTS * N)}
    bufs = {k: _sds(v, jnp.float32, s) for k, v in ring.items()}
    return rollout._collect_ring.lower(
        params, es, obs, key, bufs, _sds((), jnp.int32, s),
        *map(sds, (mc.sensor, mc.tgt, mc.masses, mc.lengths)),
        chain=mc.chain, task=mc.task, substeps=spec.substeps, dt=spec.dt,
        max_episode_len=spec.max_episode_len, num_steps=T, use_pallas=True,
        interpret=False, policy_fn=policy_apply)


def _ppo_step(s, N=1024):
    from repro.rl.ppo import PPOConfig, init_train, make_train_step
    env = make_env("Ant")
    step = make_train_step(env, PPOConfig(num_steps=T, num_epochs=1,
                                          use_fused_kernels=True))
    state = jax.eval_shape(lambda: init_train(
        jax.random.PRNGKey(0), env, env.spec.policy_dims, N))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(1))
    return step.lower(*jax.tree.map(lambda x: _sds(x.shape, x.dtype, s),
                                    (*state, key)))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels called without ``interpret`` compile for the described
    chip; traces cached either side of the patch are dropped."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    yield
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("lower,kernel", [(_collect_ring, "env_mega_step"),
                                          (_ppo_step, "gae_norm")],
                         ids=["collect_ring", "ppo_step"])
def test_pallas_instruction_names_in_their_programs(one_chip,
                                                    compiled_kernels,
                                                    lower, kernel):
    names = _pallas_names(lower(one_chip).compile().as_text())
    assert len(names) == 1 and names[0].startswith(kernel), names


def _a3c_update(s, N=2 * N_ENVS):
    """The async runner's compiled update at the Ant cell's batch."""
    from repro.rl.a3c import AsyncRunner, Experience
    runner = AsyncRunner(make_env("Ant"), [0], [100], num_envs=8,
                         num_steps=T, use_fused_kernels=True)
    f32 = jnp.float32
    exp = Experience(obs=_sds((T, N, 60), f32, s),
                     actions=_sds((T, N, 8), f32, s),
                     rewards=_sds((T, N), f32, s), dones=_sds((T, N), f32, s),
                     bootstrap=_sds((N,), f32, s),
                     actor_version=_sds((), jnp.int32, s))
    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, s),
                         (runner.params, runner.opt_state))
    return runner._update.lower(*state, exp)


def test_a3c_update_runs_nstep_returns_outside_the_gradient_on_hbm(
        one_chip, compiled_kernels):
    """``bench/metrics/nstep_roofline.py`` finds the kernel by this name
    and bounds it by HBM bandwidth: the returns are computed outside the
    gradient (under it the instruction is a ``jvp_...`` one) and read and
    write HBM, not on-chip memory (``S(1)``)."""
    text = _a3c_update(one_chip).compile().as_text()
    names = _pallas_names(text)
    assert len(names) == 1 and names[0].startswith("nstep_returns"), names
    line = next(ln for ln in text.splitlines()
                if re.match(rf"^\s*(?:ROOT )?%{re.escape(names[0])} = ", ln))
    assert "S(1)" not in line, line


def _lm_policy_step(s, G=2, S=256):
    """Moonlight's GRPO step at its published widths, the dense layer and
    one MoE layer, on short rows."""
    from repro.configs.moonlight_16b_a3b import EP8
    from repro.launch.steps import make_lm_policy_train_step
    from repro.models.transformer import latent_moe_shapes
    from repro.optim import adam_init
    from repro.rl.grpo import GRPOConfig, TokenBatch
    cfg = EP8.replace(num_layers=2)
    params = jax.tree.map(lambda x: _sds(x, jnp.float32, s),
                          latent_moe_shapes(cfg),
                          is_leaf=lambda x: isinstance(x, tuple))
    opt = jax.tree.map(lambda x: _sds(x.shape, x.dtype, s),
                       jax.eval_shape(adam_init, params))
    batch = TokenBatch(_sds((G, S), jnp.int32, s), _sds((G, S), jnp.bool_, s),
                       _sds((G, S - 1), jnp.float32, s),
                       _sds((G,), jnp.float32, s))
    step = make_lm_policy_train_step(cfg, GRPOConfig(num_minibatches=1))
    return step.lower(params, opt, batch)


def test_lm_policy_step_runs_the_grouped_kernels_by_name(one_chip,
                                                         compiled_kernels):
    """``bench/metrics/expert_gmm_roofline.py`` finds the grouped expert
    kernel's calls by these names: ``gmm.N`` (forward and input gradient)
    and ``tgmm.N`` (weight gradient), after megablox's jitted wrappers."""
    names = _pallas_names(_lm_policy_step(one_chip).compile().as_text())
    assert {n.split(".")[0] for n in names} == {"gmm", "tgmm"}, names
