"""Articulated-chain rigid-body dynamics core.

A deliberately non-GEMM workload (transcendental-heavy, sequential substeps,
branchy contacts) mirroring the paper's observation that physics simulation
scales poorly on matrix-unit-centric accelerators: this is the component that
leaves the MXU idle and motivates spatial multiplexing.

Model: J torque-controlled joints in a kinematic chain attached to a floating
root.  Per substep (semi-implicit Euler):
  qdd_i = (tau_i - damping*qd_i - g*m_i*l_i*sin(q_i)
           + coupling*(q_{i-1} - 2 q_i + q_{i+1})) / I_i
with ground contact on the chain tip (one-sided spring-damper) and root
dynamics driven by net joint reaction.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ChainParams(NamedTuple):
    masses: jax.Array     # (J,)
    lengths: jax.Array    # (J,)
    damping: float
    coupling: float
    stiffness: float      # restoring spring toward q=0 (joint limits)
    max_qd: float
    gravity: float
    torque_scale: float
    ground_k: float       # contact spring
    ground_c: float       # contact damper


def default_params(num_joints: int, *, damping=0.5, coupling=0.6,
                   stiffness=2.0, max_qd=8.0, gravity=9.81, torque_scale=3.0,
                   ground_k=60.0, ground_c=2.0) -> ChainParams:
    idx = jnp.arange(num_joints, dtype=jnp.float32)
    masses = 1.0 + 0.15 * jnp.cos(idx)
    lengths = 0.35 + 0.05 * jnp.sin(1.7 * idx)
    return ChainParams(masses, lengths, damping, coupling, stiffness, max_qd,
                       gravity, torque_scale, ground_k, ground_c)


def tip_height(q, root_z, params: ChainParams):
    """Height of the chain tip (forward kinematics along the chain)."""
    angles = jnp.cumsum(q)
    return root_z + jnp.sum(params.lengths * jnp.cos(angles))


# --------------------------------------------------- counter-based PRNG ---
# Auto-reset used to thread a threefry key through every env state and pay
# a ``jax.random.split`` + ``normal`` per env per step whether or not the
# env was done.  Fresh states are instead a pure function of a per-env
# ``seed`` and a ``resets`` counter: an integer-hash (Murmur3 finalizer)
# feeding Box-Muller.  Every op below (xor/shift/mul on uint32, sqrt, log,
# cos) maps 1:1 onto Pallas-supported primitives, so the vmapped oracle
# reset and the megakernel's in-kernel reset produce identical values.

def hash_u32(x):
    """Murmur3 fmix32: a well-mixed uint32 -> uint32 bijection."""
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def counter_normal(seed, counter, idx):
    """Standard normals, one per (seed, counter, idx) triple.

    ``seed``/``counter``/``idx`` broadcast together; callers supply
    ``idx`` (e.g. ``jnp.arange(J, dtype=jnp.uint32)`` outside a kernel,
    ``broadcasted_iota`` inside one).  Deterministic and split-free: the
    same triple always yields the same draw, so a materialized reset and
    a predicated in-kernel reset agree bitwise."""
    s = jnp.asarray(seed, jnp.uint32)
    c = jnp.asarray(counter, jnp.uint32)
    i = jnp.asarray(idx, jnp.uint32)
    base = hash_u32(s ^ (c * jnp.uint32(0x9E3779B9)))
    h1 = hash_u32(base + i * jnp.uint32(2) + jnp.uint32(1))
    h2 = hash_u32(base + i * jnp.uint32(2) + jnp.uint32(2))
    # 24-bit mantissa uniforms; u1 offset into (0, 1] so log never sees 0.
    # The 24-bit value goes to float through int32 (exact, and the cast
    # Mosaic lowers; it has no uint32 -> float32 conversion)
    def unit(h):
        return (h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
            * (1.0 / 16777216.0)
    u1 = unit(h1) + (0.5 / 16777216.0)
    u2 = unit(h2)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    return r * jnp.cos((2.0 * jnp.pi) * u2)


def substep(q, qd, root, tau, params: ChainParams, dt: float):
    J = q.shape[0]
    # neighbor coupling (tridiagonal spring network)
    q_pad = jnp.pad(q, (1, 1), mode="edge")
    lap = q_pad[:-2] - 2.0 * q + q_pad[2:]
    inertia = params.masses * jnp.square(params.lengths) + 1e-3
    grav = params.gravity * params.masses * params.lengths * jnp.sin(q)
    qdd = (params.torque_scale * tau - params.damping * qd
           - params.stiffness * q - grav + params.coupling * lap) / inertia
    qd = jnp.clip(qd + dt * qdd, -params.max_qd, params.max_qd)
    q = q + dt * qd

    # root: driven by mean joint reaction, with ground contact at tip
    tip_h = tip_height(q, root[2], params)
    pen = jnp.maximum(-tip_h, 0.0)
    contact_f = params.ground_k * pen - params.ground_c * jnp.minimum(
        root[5], 0.0) * (pen > 0)
    thrust = jnp.array([
        jnp.mean(jnp.sin(q) * tau) * params.torque_scale,   # forward
        0.1 * jnp.mean(jnp.cos(2 * q) * tau),               # lateral drift
        contact_f - params.gravity * 0.5,                   # vertical
    ])
    vel = root[3:] + dt * thrust
    vel = vel * (1.0 - 0.02)                                # air drag
    pos = root[:3] + dt * vel
    pos = pos.at[2].set(jnp.maximum(pos[2], 0.05))
    return q, qd, jnp.concatenate([pos, vel])


def rollout_substeps(q, qd, root, tau, params: ChainParams, dt: float,
                     substeps: int):
    def body(i, carry):
        q, qd, root = carry
        return substep(q, qd, root, tau, params, dt / substeps)
    return jax.lax.fori_loop(0, substeps, body, (q, qd, root))
