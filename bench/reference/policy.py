"""Reference of the Table-6 actor-critic MLP and of Adam.

The actor is ``in:h1:...:hk:act`` with tanh hidden layers, a
diagonal-Gaussian head (state-independent log-std) and a value head off
the last hidden layer; weights He-normal from the seed, the action head
scaled by 0.01, biases and log-std zero.  ``dtype`` is the precision the
policy is held and computed in: float32 with every product at HIGHEST is
the reference; bfloat16 is the control (the step below the
configuration's float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def precision_of(dtype):
    return HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def init(key, dims, dtype=jnp.float32):
    """He-normal init drawn key by key as the published recipe does:
    ``len(dims)`` keys, trunk layer i from key i, the action head from the
    second-to-last key and the value head from the last."""
    keys = jax.random.split(key, len(dims))

    def he(k, shape):
        return jax.random.normal(k, shape, jnp.float32) \
            * (2.0 / shape[0]) ** 0.5

    p = {"trunk": [{"w": he(keys[i], (dims[i], dims[i + 1])),
                    "b": jnp.zeros((dims[i + 1],), jnp.float32)}
                   for i in range(len(dims) - 2)],
         "mu": {"w": he(keys[-2], (dims[-2], dims[-1])) * 0.01,
                "b": jnp.zeros((dims[-1],), jnp.float32)},
         "log_std": jnp.zeros((dims[-1],), jnp.float32),
         "value": {"w": he(keys[-1], (dims[-2], 1)),
                   "b": jnp.zeros((1,), jnp.float32)}}
    return jax.tree.map(lambda x: x.astype(dtype), p)


def apply(params, obs):
    """obs (..., in) -> (mu, log_std, value), float32 outputs."""
    dtype = params["log_std"].dtype
    prec = precision_of(dtype)
    h = obs.astype(dtype)
    for lyr in params["trunk"]:
        h = jnp.tanh(jnp.dot(h, lyr["w"], precision=prec) + lyr["b"])
    mu = jnp.dot(h, params["mu"]["w"], precision=prec) + params["mu"]["b"]
    value = (jnp.dot(h, params["value"]["w"], precision=prec)
             + params["value"]["b"])[..., 0]
    log_std = jnp.broadcast_to(params["log_std"], mu.shape)
    f32 = jnp.float32
    return mu.astype(f32), log_std.astype(f32), value.astype(f32)


def sample(key, mu, log_std):
    return mu + jnp.exp(log_std) * jax.random.normal(key, mu.shape)


def log_prob(mu, log_std, action):
    return jnp.sum(-0.5 * (jnp.square(action - mu) / jnp.exp(2 * log_std)
                           + 2 * log_std + math.log(2 * math.pi)), axis=-1)


def entropy(log_std):
    return jnp.sum(log_std + 0.5 * math.log(2 * math.pi * math.e), axis=-1)


def adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"step": jnp.zeros((), jnp.int32), "mu": z,
            "nu": jax.tree.map(jnp.zeros_like, params)}


def adam(grads, state, params, *, lr, beta1, beta2, eps, clip):
    """Adam (Kingma & Ba) after clipping the gradient's global norm to
    ``clip``; moments held in the parameters' dtype."""
    step = state["step"] + 1
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
    t = step.astype(jnp.float32)
    c1, c2 = 1.0 - beta1 ** t, 1.0 - beta2 ** t

    def upd(g, m, v, p):
        g = (g.astype(jnp.float32) * scale)
        m = beta1 * m.astype(jnp.float32) + (1 - beta1) * g
        v = beta2 * v.astype(jnp.float32) + (1 - beta2) * jnp.square(g)
        new = p.astype(jnp.float32) \
            - lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
        return new.astype(p.dtype), m.astype(p.dtype), v.astype(p.dtype)

    out = jax.tree.map(upd, grads, state["mu"], state["nu"], params)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), {"step": step, "mu": pick(1), "nu": pick(2)}
