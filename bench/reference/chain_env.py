"""Reference of the chain-physics env family (the configurations' stand-in
for Isaac Gym's PhysX), written from its equations.

J torque-controlled joints in a chain on a floating root, semi-implicit
Euler substeps, ground contact on the chain tip, a fixed orthonormal
sensor projection to the published observation width, and a counter-based
auto-reset: a fresh state is a pure function of the env's stream id and
its reset count (Murmur3 finalizer feeding Box-Muller).  Every constant
comes from the configuration file's ``env`` group.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class State(NamedTuple):
    q: jax.Array
    qd: jax.Array
    root: jax.Array
    prev_action: jax.Array
    t: jax.Array
    seed: jax.Array
    resets: jax.Array


def _fmix32(x):
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _counter_normal(seed, counter, idx):
    base = _fmix32(jnp.asarray(seed, jnp.uint32)
                   ^ (jnp.asarray(counter, jnp.uint32)
                      * jnp.uint32(0x9E3779B9)))
    i = jnp.asarray(idx, jnp.uint32)
    h1 = _fmix32(base + i * jnp.uint32(2) + jnp.uint32(1))
    h2 = _fmix32(base + i * jnp.uint32(2) + jnp.uint32(2))
    u1 = (h1 >> 8).astype(jnp.int32).astype(jnp.float32) / 16777216.0 \
        + 0.5 / 16777216.0
    u2 = (h2 >> 8).astype(jnp.int32).astype(jnp.float32) / 16777216.0
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)


def _sensor(name: str, raw_dim: int, obs_dim: int) -> np.ndarray:
    rng = np.random.RandomState(zlib.crc32(name.encode()) % (2 ** 31))
    m = rng.randn(raw_dim, obs_dim).astype(np.float32)
    if raw_dim >= obs_dim:
        out = np.linalg.qr(m)[0][:, :obs_dim]
    else:
        out = np.linalg.qr(m.T)[0][:, :raw_dim].T
    return (out * np.sqrt(2.0)).astype(np.float32)


class ChainEnv:
    """Vectorized reference env: ``reset(key, n)`` and ``step(state, a)``
    on ``(n, ...)`` stacked states, with the auto-reset materialized."""

    def __init__(self, env_cfg: dict):
        self.cfg = env_cfg
        J = self.J = int(env_cfg["act_dim"])
        self.obs_dim = int(env_cfg["obs_dim"])
        idx = np.arange(J, dtype=np.float32)
        self.masses = jnp.asarray(1.0 + 0.15 * np.cos(idx), jnp.float32)
        self.lengths = jnp.asarray(0.35 + 0.05 * np.sin(1.7 * idx),
                                   jnp.float32)
        self.tgt = jnp.asarray(np.random.RandomState(
            env_cfg["target_seed"]).uniform(-0.6, 0.6, size=(J,))
            .astype(np.float32))
        self.sensor = jnp.asarray(_sensor(env_cfg["name"], 6 + 4 * J + 3,
                                          self.obs_dim))
        self.c = env_cfg["chain"]
        self.task = env_cfg["task"]

    def _fresh(self, seed, resets):
        J = self.J
        return State(
            q=0.1 * _counter_normal(seed, resets,
                                    jnp.arange(J, dtype=jnp.uint32)),
            qd=jnp.zeros((J,)), root=jnp.array([0., 0., 0.6, 0., 0., 0.]),
            prev_action=jnp.zeros((J,)), t=jnp.zeros((), jnp.int32),
            seed=jnp.asarray(seed, jnp.int32),
            resets=jnp.asarray(resets, jnp.int32))

    def _tip(self, q, root_z):
        return root_z + jnp.sum(self.lengths * jnp.cos(jnp.cumsum(q)))

    def _obs(self, s: State):
        raw = jnp.concatenate([
            s.root, jnp.sin(s.q), jnp.cos(s.q), s.qd, s.prev_action,
            jnp.array([self._tip(s.q, s.root[2]), s.root[2] - 0.6,
                       jnp.mean(jnp.abs(s.qd))])])
        return jnp.tanh(jnp.dot(raw, self.sensor, precision=HIGHEST))

    def _substep(self, q, qd, root, a, h):
        c = self.c
        qp = jnp.concatenate([q[:1], q, q[-1:]])
        lap = qp[:-2] - 2.0 * q + qp[2:]
        inertia = self.masses * jnp.square(self.lengths) + 1e-3
        grav = c["gravity"] * self.masses * self.lengths * jnp.sin(q)
        qdd = (c["torque_scale"] * a - c["damping"] * qd
               - c["stiffness"] * q - grav + c["coupling"] * lap) / inertia
        qd = jnp.clip(qd + h * qdd, -c["max_qd"], c["max_qd"])
        q = q + h * qd
        pen = jnp.maximum(-self._tip(q, root[2]), 0.0)
        contact = c["ground_k"] * pen \
            - c["ground_c"] * jnp.minimum(root[5], 0.0) * (pen > 0)
        thrust = jnp.array([
            jnp.mean(jnp.sin(q) * a) * c["torque_scale"],
            0.1 * jnp.mean(jnp.cos(2 * q) * a),
            contact - c["gravity"] * 0.5])
        vel = (root[3:] + h * thrust) * (1.0 - 0.02)
        pos = root[:3] + h * vel
        pos = pos.at[2].set(jnp.maximum(pos[2], 0.05))
        return q, qd, jnp.concatenate([pos, vel])

    def _step_one(self, s: State, action):
        w_fwd, w_up, w_ctrl, w_tgt, fall_z = self.task
        cfg = self.cfg
        a = jnp.clip(action, -1.0, 1.0)
        h = cfg["dt"] / cfg["substeps"]
        q, qd, root = s.q, s.qd, s.root
        for _ in range(cfg["substeps"]):
            q, qd, root = self._substep(q, qd, root, a, h)
        reward = (w_fwd * root[3] + w_up * jnp.cos(jnp.mean(q))
                  - w_ctrl * jnp.sum(jnp.square(a))
                  - w_tgt * jnp.mean(jnp.square(q - self.tgt)) + 0.5)
        t = s.t + 1
        done = (t >= cfg["max_episode_len"]) | (root[2] < fall_z)
        stepped = State(q, qd, root, a, t, s.seed, s.resets)
        fresh = self._fresh(s.seed, s.resets + 1)
        out = jax.tree.map(lambda x, y: jnp.where(done, y, x), stepped,
                           fresh)
        return out, reward, done

    def reset(self, key, n: int):
        seeds = jax.random.randint(key, (n,), 0, jnp.iinfo(jnp.int32).max,
                                   dtype=jnp.int32)
        state = jax.vmap(self._fresh)(seeds, jnp.zeros((n,), jnp.int32))
        return state, jax.vmap(self._obs)(state)

    def step(self, state: State, action):
        """-> (state, obs, reward, done); obs after any auto-reset."""
        state, reward, done = jax.vmap(self._step_one)(state, action)
        return state, jax.vmap(self._obs)(state), reward, done
