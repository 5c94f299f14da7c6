"""Experience collection: the serving loop (simulator <-> agent interaction).

``collect`` is the paper's "DRL serving block": the simulator and the agent
execute sequentially inside one jitted scan — the TCG (task-colocated GMI)
template, where state/action sharing is an intra-instance memory access
(COM = 0, Table 4).

``collect_ring`` is its zero-copy producer sibling for megakernel envs:
the same scan, but each step runs the fused env megakernel
(``kernels/env_megakernel.py``) which writes obs/action/reward/done
straight into the caller's ``ChannelRing`` slot buffers — no Trajectory
is staged, nothing is re-packed by ``pack_channels``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.policy import log_prob, policy_apply, sample_action


class Trajectory(NamedTuple):
    obs: jax.Array       # (T, N, obs_dim)
    actions: jax.Array   # (T, N, act_dim)
    log_probs: jax.Array # (T, N)
    rewards: jax.Array   # (T, N)
    dones: jax.Array     # (T, N)
    values: jax.Array    # (T, N)


def collect(policy_params, env, env_state, obs, key, num_steps: int,
            policy_fn=policy_apply):
    """Roll the policy for ``num_steps`` across all vectorized envs.

    Returns (traj, env_state, last_obs, last_value, key).
    """

    def step(carry, _):
        env_state, obs, key = carry
        key, akey = jax.random.split(key)
        mu, log_std, value = policy_fn(policy_params, obs)
        action = sample_action(akey, mu, log_std)
        lp = log_prob(mu, log_std, action)
        env_state, next_obs, reward, done = env.step(env_state, action)
        out = (obs, action, lp, reward, done.astype(jnp.float32), value)
        return (env_state, next_obs, key), out

    (env_state, obs, key), outs = jax.lax.scan(
        step, (env_state, obs, key), None, length=num_steps)
    traj = Trajectory(*outs)
    _, _, last_value = policy_fn(policy_params, obs)
    return traj, env_state, obs, last_value, key


@functools.partial(
    jax.jit, donate_argnums=(4,),
    static_argnames=("chain", "task", "substeps", "dt", "max_episode_len",
                     "num_steps", "use_pallas", "interpret", "policy_fn"))
def _collect_ring(params, state, obs, key, bufs, slot, sensor, tgt, masses,
                  lengths, *, chain, task, substeps, dt, max_episode_len,
                  num_steps, use_pallas, interpret, policy_fn):
    from repro.envs.base import EnvState
    from repro.kernels.env_megakernel import env_mega_step, mega_step_ring
    slot_i = jnp.asarray(slot, jnp.int32)

    def step(carry, step_t):
        state, obs, key, bufs = carry
        key, akey = jax.random.split(key)
        mu, log_std, _ = policy_fn(params, obs)
        action = sample_action(akey, mu, log_std)
        if use_pallas:
            out = env_mega_step(
                *state, action, obs, bufs, step_t, slot_i, sensor, tgt,
                masses, lengths, chain=chain, task=task, substeps=substeps,
                dt=dt, max_episode_len=max_episode_len, interpret=interpret)
        else:
            out = mega_step_ring(
                *state, action, obs, bufs, step_t, slot_i, sensor, tgt,
                masses, lengths, chain=chain, task=task, substeps=substeps,
                dt=dt, max_episode_len=max_episode_len)
        q, qd, root, pa, t, seed, resets, next_obs = out[:8]
        return (EnvState(q, qd, root, pa, t, seed, resets), next_obs, key,
                out[10]), None

    (state, obs, key, bufs), _ = jax.lax.scan(
        step, (state, obs, key, bufs),
        jnp.arange(num_steps, dtype=jnp.int32))
    _, _, bootstrap = policy_fn(params, obs)
    return bufs, state, obs, bootstrap, key


def collect_ring(policy_params, env, env_state, obs, key, num_steps: int,
                 bufs, slot, policy_fn=policy_apply, use_pallas=None):
    """Zero-copy serving for ``VectorEnv(megakernel=True)``.

    One jitted, donated scan: per step the policy acts, then the fused
    env megakernel advances every env AND writes the experience row
    (acted-on obs, raw action, reward, done) directly into ring slot
    ``slot`` of the ``{obs, actions, rewards, dones}`` buffers ``bufs``
    — the ``ChannelRing`` layout from ``kernels/channel_pack.py``.
    ``bufs`` is donated; use the returned dict.  On TPU the step is the
    compiled Pallas megakernel; on the CPU backend the identically fused
    XLA program (``mega_step_ring``), matching the ``pack_channels``
    convention.  ``use_pallas=True`` on the CPU runs the kernel in
    interpret mode.

    Returns ``(bufs, env_state, last_obs, bootstrap, key)`` where
    ``bootstrap`` is the value of ``last_obs`` under ``policy_params``.
    """
    if not getattr(env, "megakernel", False):
        raise ValueError("collect_ring needs VectorEnv(megakernel=True); "
                         "use collect for the vmap path")
    from repro.kernels.ops import _interpret_default
    interpret = _interpret_default()
    use_pallas = (not interpret) if use_pallas is None else use_pallas
    mc = env.mega
    return _collect_ring(
        policy_params, env_state, obs, key, bufs, jnp.asarray(slot, jnp.int32),
        mc.sensor, mc.tgt, mc.masses, mc.lengths, chain=mc.chain,
        task=mc.task, substeps=env.spec.substeps, dt=env.spec.dt,
        max_episode_len=env.spec.max_episode_len, num_steps=int(num_steps),
        use_pallas=use_pallas, interpret=interpret, policy_fn=policy_fn)


def gae(rewards, values, dones, last_value, gamma: float = 0.99,
        lam: float = 0.95):
    """Generalized advantage estimation.  All inputs (T, N)."""

    def step(carry, xs):
        adv_next, v_next = carry
        r, v, d = xs
        nonterm = 1.0 - d
        delta = r + gamma * v_next * nonterm - v
        adv = delta + gamma * lam * nonterm * adv_next
        return (adv, v), adv

    (_, _), advs = jax.lax.scan(
        step, (jnp.zeros_like(last_value), last_value),
        (rewards, values, dones), reverse=True)
    returns = advs + values
    return advs, returns


def gae_fused(rewards, values, dones, last_value, gamma: float = 0.99,
              lam: float = 0.95, eps: float = 1e-8):
    """Fused Pallas GAE: one kernel computes the reverse scan, the returns,
    AND the global advantage normalization without leaving VMEM (see
    ``repro.kernels.gae_scan``).  Returns (normalized_advs, returns).

    Unlike :func:`gae`, the advantages come back already normalized over
    the whole (T, N) batch — callers must not re-normalize per minibatch.
    """
    from repro.kernels import ops
    return ops.gae_norm(rewards, values, dones, last_value, gamma=gamma,
                        lam=lam, eps=eps)
