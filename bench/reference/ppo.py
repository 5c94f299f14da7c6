"""Reference of one synchronous PPO iteration (Schulman et al.,
arXiv:1707.06347, as Isaac Gym runs it): ``num_steps`` policy steps over
every env, GAE(gamma, lambda) with the advantages normalized over the
whole batch, then ``num_epochs`` passes of ``num_minibatches`` shuffled
minibatches of clipped-surrogate + value + entropy loss, each an Adam step.

``fault="half_batch"`` takes each minibatch's loss over its first half
only: the planted fault the comparison has to catch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import policy as P


def _gae(rewards, values, dones, last_value, gamma, lam):
    def step(carry, xs):
        adv, v_next = carry
        r, v, d = xs
        nonterm = 1.0 - d
        adv = r + gamma * v_next * nonterm - v + gamma * lam * nonterm * adv
        return (adv, v), adv

    _, advs = jax.lax.scan(step, (jnp.zeros_like(last_value), last_value),
                           (rewards, values, dones), reverse=True)
    return advs, advs + values


def _loss(params, batch, a, fault):
    obs, actions, old_lp, advs, returns = batch
    if fault == "half_batch":
        half = obs.shape[0] // 2
        obs, actions, old_lp, advs, returns = (
            x[:half] for x in (obs, actions, old_lp, advs, returns))
    mu, log_std, value = P.apply(params, obs)
    ratio = jnp.exp(P.log_prob(mu, log_std, actions) - old_lp)
    pg = -jnp.minimum(ratio * advs, jnp.clip(ratio, 1 - a["clip_eps"],
                                             1 + a["clip_eps"]) * advs)
    vf = 0.5 * jnp.square(value - returns)
    return (pg.mean() + a["vf_coef"] * vf.mean()
            - a["ent_coef"] * P.entropy(log_std).mean())


def make_iteration(env, a: dict, fault=None):
    """``a`` is the traffic file's ``algo`` group.  Returns a jitted
    ``(params, opt, env_state, obs, key) -> (..., loss)``; ``loss`` is the
    mean over the iteration's minibatch losses."""
    T, E, M = a["num_steps"], a["num_epochs"], a["num_minibatches"]
    adam = dict(lr=a["lr"], beta1=a["beta1"], beta2=a["beta2"],
                eps=a["eps"], clip=a["max_grad_norm"])

    @jax.jit
    def iterate(params, opt, env_state, obs, key):
        def act(carry, _):
            env_state, obs, key = carry
            key, akey = jax.random.split(key)
            mu, log_std, value = P.apply(params, obs)
            action = P.sample(akey, mu, log_std)
            env_state, nxt, reward, done = env.step(env_state, action)
            out = (obs, action, P.log_prob(mu, log_std, action), reward,
                   done.astype(jnp.float32), value)
            return (env_state, nxt, key), out

        (env_state, obs, key), (o, ac, lp, r, d, v) = jax.lax.scan(
            act, (env_state, obs, key), None, length=T)
        last = P.apply(params, obs)[2]
        advs, rets = _gae(r, v, d, last, a["gamma"], a["lam"])
        advs = (advs - advs.mean()) / (advs.std() + 1e-8)
        N = r.shape[1]
        flat = [x.reshape((T * N,) + x.shape[2:])
                for x in (o, ac, lp, advs, rets)]

        def epoch(carry, _):
            params, opt, key = carry
            key, pkey = jax.random.split(key)
            idx = jax.random.permutation(pkey, T * N).reshape(M, -1)
            mbs = [jnp.take(x, idx, axis=0) for x in flat]

            def minibatch(carry, batch):
                params, opt = carry
                loss, grads = jax.value_and_grad(_loss)(params, batch, a,
                                                         fault)
                params, opt = P.adam(grads, opt, params, **adam)
                return (params, opt), loss

            (params, opt), losses = jax.lax.scan(minibatch, (params, opt),
                                                 mbs)
            return (params, opt, key), losses.mean()

        (params, opt, key), losses = jax.lax.scan(
            epoch, (params, opt, key), None, length=E)
        return params, opt, env_state, obs, key, losses.mean()

    return iterate
