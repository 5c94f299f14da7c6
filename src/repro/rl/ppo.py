"""PPO (Schulman et al., arXiv:1707.06347) — the paper's synchronized DRL
training workload (Isaac Gym's official algorithm).

One ``train_iteration`` = experience collection (m simulator-agent rounds)
+ minibatched clipped-surrogate updates — the two sequential stages of §5.1.
Gradient synchronization across trainer GMIs plugs in via ``grad_sync_fn``,
which accepts either a bare closure or a ``repro.comm.Communicator`` (the
communication subsystem object owning mesh + LGR strategy); identity on a
single instance.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.policy import entropy, log_prob, policy_apply
from repro.optim import AdamState, adam_init, adam_update
from repro.rl.rollout import Trajectory, collect, gae, gae_fused


class PPOConfig(NamedTuple):
    num_steps: int = 32          # m: simulator-agent rounds per iteration
    num_epochs: int = 4
    num_minibatches: int = 4
    gamma: float = 0.99
    lam: float = 0.95
    clip_eps: float = 0.2
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    lr: float = 3e-4
    max_grad_norm: float = 1.0
    # fused hot path: the Pallas GAE+normalization kernel (advantages
    # arrive batch-normalized, so the loss skips its per-minibatch
    # renormalization)
    use_fused_kernels: bool = False


def clipped_surrogate(ratio, advs, clip_eps):
    """PPO's clipped policy-gradient objective, negated (a loss), per
    sample: ``-min(r A, clip(r, 1 - eps, 1 + eps) A)``.  Shared by the
    policy-MLP loss below and the LLM-policy step (``rl/grpo.py``)."""
    return -jnp.minimum(ratio * advs,
                        jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * advs)


def ppo_loss(params, batch, clip_eps, vf_coef, ent_coef,
             policy_fn=policy_apply, normalize_adv: bool = True):
    obs, actions, old_lp, advs, returns = batch
    mu, log_std, value = policy_fn(params, obs)
    lp = log_prob(mu, log_std, actions)
    ratio = jnp.exp(lp - old_lp)
    advs_n = (advs - advs.mean()) / (advs.std() + 1e-8) \
        if normalize_adv else advs
    pg = clipped_surrogate(ratio, advs_n, clip_eps)
    vf = 0.5 * jnp.square(value - returns)
    ent = entropy(log_std)
    loss = pg.mean() + vf_coef * vf.mean() - ent_coef * ent.mean()
    return loss, (pg.mean(), vf.mean(), ent.mean())


def pack_rows(fields):
    """Pack arrays that share their leading (row) axis into one table.

    Each field of the first field's dtype is flattened to ``(rows, width)``
    and the fields are concatenated along the columns, at their natural
    width.  A row gather of the table moves every field of a sample at
    once: on the TPU a row gather costs per row, hardly per byte, so one
    table replaces one gather per field.  A field of another dtype stays a
    table of its own.

    Returns ``(tables, unpack)``: ``unpack`` takes the tables gathered by
    one index array (or any slice of them that keeps the column axis
    last), in order, and gives the fields back in their order and
    trailing shapes.
    """
    rows, dtype = fields[0].shape[0], fields[0].dtype
    cols = [f.reshape(rows, -1) for f in fields if f.dtype == dtype]
    tables = [jnp.concatenate(cols, axis=1)]
    tables += [f for f in fields if f.dtype != dtype]

    def unpack(gathered):
        table, *own = gathered
        lead, at, own = table.shape[:-1], 0, iter(own)
        out = []
        for f in fields:
            if f.dtype != dtype:
                out.append(next(own))
                continue
            width = math.prod(f.shape[1:])
            out.append(table[..., at:at + width].reshape(lead + f.shape[1:]))
            at += width
        return tuple(out)

    return tables, unpack


def train_iteration(params, opt_state: AdamState, env, env_state, obs, key,
                    cfg: PPOConfig, grad_sync_fn: Optional[Callable] = None,
                    policy_fn=policy_apply):
    """One full PPO iteration.  Returns (params, opt_state, env_state, obs,
    key, metrics).  ``grad_sync_fn`` may be a closure or a Communicator."""
    from repro.comm.api import as_grad_sync   # lazy: rl <-> comm layering
    grad_sync_fn = as_grad_sync(grad_sync_fn)
    # named scopes label the step's device ops by phase in a profile
    with jax.named_scope("ppo/collect"):
        traj, env_state, obs, last_value, key = collect(
            params, env, env_state, obs, key, cfg.num_steps, policy_fn)
    with jax.named_scope("ppo/gae"):
        if cfg.use_fused_kernels:
            # fused Pallas kernel: advantages arrive normalized over the
            # batch
            advs, returns = gae_fused(traj.rewards, traj.values,
                                      traj.dones, last_value, cfg.gamma,
                                      cfg.lam)
        else:
            advs, returns = gae(traj.rewards, traj.values, traj.dones,
                                last_value, cfg.gamma, cfg.lam)

    T, N = traj.rewards.shape
    with jax.named_scope("ppo/shuffle"):
        tables, unpack = pack_rows(
            [x.reshape((T * N,) + x.shape[2:])
             for x in (traj.obs, traj.actions, traj.log_probs, advs,
                       returns)])
    mb_size = (T * N) // cfg.num_minibatches

    def epoch(carry, _):
        params, opt_state, key = carry
        with jax.named_scope("ppo/shuffle"):
            key, pkey = jax.random.split(key)
            perm = jax.random.permutation(pkey, T * N)
            # one row gather per table, straight into minibatch layout; the
            # fields are sliced out in the minibatch body, where the slices
            # fuse into their uses, rather than each field of the whole
            # epoch being copied out of the table (PERF.md section 5)
            idx = perm.reshape((cfg.num_minibatches, mb_size))
            mb = [t[idx] for t in tables]

        def minibatch(carry, batch):
            params, opt_state = carry
            batch = unpack(batch)
            with jax.named_scope("ppo/loss_grad"):
                (loss, aux), grads = jax.value_and_grad(
                    ppo_loss, has_aux=True)(
                        params, batch, cfg.clip_eps, cfg.vf_coef,
                        cfg.ent_coef, policy_fn, not cfg.use_fused_kernels)
            if grad_sync_fn is not None:
                grads = grad_sync_fn(grads)
            with jax.named_scope("ppo/adam"):
                params, opt_state = adam_update(
                    grads, opt_state, params, lr=cfg.lr, beta1=0.9,
                    beta2=0.999, grad_clip=cfg.max_grad_norm)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(minibatch,
                                                   (params, opt_state), mb)
        return (params, opt_state, key), losses.mean()

    (params, opt_state, key), losses = jax.lax.scan(
        epoch, (params, opt_state, key), None, length=cfg.num_epochs)

    metrics = {
        "loss": losses.mean(),
        "reward_mean": traj.rewards.mean(),
        "reward_sum": traj.rewards.sum(0).mean(),
        "episode_done_frac": traj.dones.mean(),
        "steps": jnp.float32(T * N),
    }
    return params, opt_state, env_state, obs, key, metrics


def make_train_step(env, cfg: PPOConfig, grad_sync_fn=None,
                    policy_fn=policy_apply):
    """jit-compiled PPO iteration bound to an env instance.

    ``grad_sync_fn`` may be a closure or a ``repro.comm.Communicator`` —
    resolved once here so the jitted step holds a stable callable."""
    from repro.comm.api import as_grad_sync   # lazy: rl <-> comm layering
    grad_sync_fn = as_grad_sync(grad_sync_fn)

    # donate only the env state: params may be SHARED between GMI instances
    # right after a global policy sync (donating would delete the shared
    # buffer under the other instances)
    @functools.partial(jax.jit, donate_argnums=(2,))
    def step(params, opt_state, env_state, obs, key):
        return train_iteration(params, opt_state, env, env_state, obs, key,
                               cfg, grad_sync_fn, policy_fn)

    return step


def init_train(key, env, policy_dims, num_envs: int):
    from repro.models.policy import init_policy
    kp, ke = jax.random.split(key)
    params = init_policy(kp, policy_dims)
    opt_state = adam_init(params)
    env_state, obs = env.reset(ke, num_envs)
    return params, opt_state, env_state, obs
