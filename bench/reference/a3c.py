"""Reference of one asynchronous A3C round (Mnih et al., ICML'16) as
GMI-DRL decouples it: every serving instance rolls ``num_steps`` steps of
its own envs with the policy snapshot taken after the previous round's
update, the trainer consumes the round's experience as one batch (the
instances' envs side by side, in instance order) with n-step returns
bootstrapped from each instance's last value, one Adam step, and the
snapshot is refreshed.

``fault="half_batch"`` takes the loss over the first half of the batch's
envs only: the planted fault the comparison has to catch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import policy as P


def make_collect(env, num_steps: int):
    @jax.jit
    def collect(params, env_state, obs, key):
        def act(carry, _):
            env_state, obs, key = carry
            key, akey = jax.random.split(key)
            mu, log_std, _ = P.apply(params, obs)
            action = P.sample(akey, mu, log_std)
            env_state, nxt, reward, done = env.step(env_state, action)
            return (env_state, nxt, key), (obs, action, reward,
                                           done.astype(jnp.float32))

        (env_state, obs, key), exp = jax.lax.scan(
            act, (env_state, obs, key), None, length=num_steps)
        return exp, P.apply(params, obs)[2], env_state, obs, key

    return collect


def make_train(a: dict, fault=None):
    adam = dict(lr=a["lr"], beta1=a["beta1"], beta2=a["beta2"],
                eps=a["eps"], clip=a["max_grad_norm"])

    def loss_fn(params, obs, actions, rewards, dones, bootstrap):
        if fault == "half_batch":
            half = rewards.shape[1] // 2
            obs, actions, rewards, dones = (
                x[:, :half] for x in (obs, actions, rewards, dones))
            bootstrap = bootstrap[:half]

        def back(g, xs):
            r, d = xs
            g = r + a["gamma"] * g * (1.0 - d)
            return g, g

        _, rets = jax.lax.scan(back, bootstrap, (rewards, dones),
                               reverse=True)
        mu, log_std, value = P.apply(params, obs)
        adv = rets - value
        pg = -(P.log_prob(mu, log_std, actions)
               * jax.lax.stop_gradient(adv)).mean()
        return (pg + a["vf_coef"] * 0.5 * jnp.square(adv).mean()
                - a["ent_coef"] * P.entropy(log_std).mean())

    @jax.jit
    def train(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        params, opt = P.adam(grads, opt, params, **adam)
        return params, opt, loss

    return train
