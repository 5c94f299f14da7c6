"""The paper's six DRL benchmarks (Table 6) as vectorized JAX environments.

| name          | abbr | type | obs | policy (Table 6)        | act |
| Ant           | AT   | L    |  60 | 60:256:128:64:8         |  8  |
| Anymal        | AY   | L    |  48 | 48:256:128:64:12        | 12  |
| BallBalance   | BB   | L    |  24 | 24:256:128:64:3         |  3  |
| FrankaCabinet | FC   | F    |  23 | 23:256:128:64:9         |  9  |
| Humanoid      | HM   | L    | 108 | 108:200:400:100:21      | 21  |
| ShadowHand    | SH   | R    | 211 | 211:512:512:512:256:20  | 20  |

Each env drives the articulated-chain core with task-specific parameters,
reward shaping, and a fixed orthonormal "sensor mixing" projection that maps
raw physical features to exactly the published observation dimension.
"""
from __future__ import annotations

import zlib

import numpy as np

import jax
import jax.numpy as jnp

from repro.envs.base import EnvSpec, EnvState, MegaConsts, VectorEnv
from repro.envs.physics import (counter_normal, default_params,
                                rollout_substeps, tip_height)

SPECS = {
    "Ant":           EnvSpec("Ant", "AT", 60, 8, "L", (60, 256, 128, 64, 8)),
    "Anymal":        EnvSpec("Anymal", "AY", 48, 12, "L", (48, 256, 128, 64, 12)),
    "BallBalance":   EnvSpec("BallBalance", "BB", 24, 3, "L", (24, 256, 128, 64, 3)),
    "FrankaCabinet": EnvSpec("FrankaCabinet", "FC", 23, 9, "F", (23, 256, 128, 64, 9)),
    "Humanoid":      EnvSpec("Humanoid", "HM", 108, 21, "L", (108, 200, 400, 100, 21)),
    "ShadowHand":    EnvSpec("ShadowHand", "SH", 211, 20, "R", (211, 512, 512, 512, 256, 20)),
}

_TASK = {
    # (w_forward, w_upright, w_ctrl, w_target, fall_z)
    "Ant":           (1.0, 0.2, 0.005, 0.0, 0.12),
    "Anymal":        (1.0, 0.4, 0.01, 0.0, 0.15),
    "BallBalance":   (0.0, 0.0, 0.002, 1.0, -1.0),
    "FrankaCabinet": (0.0, 0.0, 0.005, 1.5, -1.0),
    "Humanoid":      (1.2, 0.6, 0.01, 0.0, 0.25),
    "ShadowHand":    (0.0, 0.0, 0.002, 2.0, -1.0),
}


def sensor_seed(name: str) -> int:
    """Process-independent seed of an env's sensor matrix."""
    return zlib.crc32(name.encode()) % (2 ** 31)


def _sensor_matrix(name: str, raw_dim: int, obs_dim: int) -> jnp.ndarray:
    """Fixed orthonormal-ish projection raw -> obs (deterministic per env).

    Seeded from a CRC-32 of the name, not ``hash``: Python salts string
    hashes per process, which made the env (and the compiled vmap path,
    where this matrix is a constant) differ from run to run."""
    seed = sensor_seed(name)
    rng = np.random.RandomState(seed)
    m = rng.randn(raw_dim, obs_dim).astype(np.float32)
    # orthonormalize columns where possible for a well-conditioned sensor map
    q, _ = np.linalg.qr(m) if raw_dim >= obs_dim else np.linalg.qr(m.T)
    out = q[:, :obs_dim] if raw_dim >= obs_dim else q[:, :raw_dim].T
    return jnp.asarray(out * np.sqrt(2.0))


def make_env(name: str, megakernel: bool = False) -> VectorEnv:
    spec = SPECS[name]
    J = spec.act_dim
    params = default_params(J)
    w_fwd, w_up, w_ctrl, w_tgt, fall_z = _TASK[name]
    # task target configuration (manipulation tasks track it)
    tgt = jnp.asarray(np.random.RandomState(7).uniform(
        -0.6, 0.6, size=(J,)).astype(np.float32))
    raw_dim = 6 + 4 * J + 3          # root + sinq/cosq/qd/prev_act + extras
    sensor = _sensor_matrix(name, raw_dim, spec.obs_dim)

    def reset_fn(seed, resets) -> EnvState:
        # fresh state as a pure function of (seed, resets): shared with the
        # megakernel's predicated in-kernel reset, draw for draw
        q0 = 0.1 * counter_normal(seed, resets,
                                  jnp.arange(J, dtype=jnp.uint32))
        return EnvState(
            q=q0,
            qd=jnp.zeros((J,)),
            root=jnp.array([0., 0., 0.6, 0., 0., 0.]),
            prev_action=jnp.zeros((J,)),
            t=jnp.zeros((), jnp.int32),
            seed=jnp.asarray(seed, jnp.int32),
            resets=jnp.asarray(resets, jnp.int32))

    def obs_fn(state: EnvState):
        tip = tip_height(state.q, state.root[2], params)
        raw = jnp.concatenate([
            state.root,
            jnp.sin(state.q), jnp.cos(state.q), state.qd,
            state.prev_action,
            jnp.array([tip, state.root[2] - 0.6,
                       jnp.mean(jnp.abs(state.qd))]),
        ])
        return jnp.tanh(raw @ sensor)

    def step_fn(state: EnvState, action):
        a = jnp.clip(action, -1.0, 1.0)
        q, qd, root = rollout_substeps(state.q, state.qd, state.root, a,
                                       params, spec.dt, spec.substeps)
        upright = jnp.cos(jnp.mean(q))
        reward = (w_fwd * root[3]
                  + w_up * upright
                  - w_ctrl * jnp.sum(jnp.square(a))
                  - w_tgt * jnp.mean(jnp.square(q - tgt))
                  + 0.5)                                     # alive bonus
        t = state.t + 1
        fell = root[2] < fall_z
        done = (t >= spec.max_episode_len) | fell
        new_state = EnvState(q=q, qd=qd, root=root, prev_action=a, t=t,
                             seed=state.seed, resets=state.resets)
        return new_state, reward, done

    mega = MegaConsts(
        sensor=sensor, tgt=tgt, masses=params.masses, lengths=params.lengths,
        chain=(params.damping, params.coupling, params.stiffness,
               params.max_qd, params.gravity, params.torque_scale,
               params.ground_k, params.ground_c),
        task=(w_fwd, w_up, w_ctrl, w_tgt, fall_z))
    return VectorEnv(spec, reset_fn, step_fn, obs_fn, mega=mega,
                     megakernel=megakernel)


def all_env_names():
    return list(SPECS.keys())
