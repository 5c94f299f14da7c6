"""Fused on-device environment megakernel (WarpDrive-style, ROADMAP 3).

One Pallas kernel advances a whole block of envs by one policy step:
chain-physics substep loop x ``spec.substeps``, reward, episode
bookkeeping, *predicated* auto-reset (fresh states are computed only when
some env in the block is done, from the counter-based PRNG in
``envs/physics.py`` — no per-step ``jax.random.split``), the next
observation, AND the producer-side experience write: obs/action/reward/
done land directly in the ``ChannelRing`` slot layout that
``kernels/channel_pack.py`` owns, so a rollout never stages a Trajectory
for ``pack_channels`` to re-copy.

Slot-write contract: for ring buffers shaped ``(T, S*N, ...)`` and a
rollout writing slot ``s``, the kernel invoked at step ``t`` over env
block ``i`` (of ``N // block_envs``) writes rows
``[t, s*N + i*BE : s*N + (i+1)*BE]`` — the obs the policy acted on, the
raw sampled action, and the step's reward/done.  ``(t, slot, N)`` ride
the scalar-prefetch operand so neither retraces the kernel.

The grid runs over env blocks; per-env state arrays are blocked
``(block_envs, ...)``.  Per-env scalars (step counter, seed, reset
count, reward, done) cross the kernel boundary as ``(1, N)`` rows and
are columns ``(block_envs, 1)`` inside it, so they broadcast against the
``(block_envs, J)`` joint arrays.  The obs and action rings stay in HBM
(``pl.ANY``) aliased to the outputs, whose ``(block_envs, dim)`` blocks
the scalar prefetch addresses at row ``t``, so untouched slots survive
the call.  The reward and done rings are lane-major ``(T, S*N)``: one row
of them is a sub-tile slice that neither a block nor a DMA may address,
so those two rows land by ``dynamic_update_slice`` in the same program.
``mega_step`` is the identically fused XLA program (shared
``_step_core``), the reference the Pallas kernel is checked against and
the env step of ``VectorEnv(megakernel=True)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.envs.physics import counter_normal


_HI = jax.lax.Precision.HIGHEST


def _tip_height(root_z, q, lengths):
    """Chain-tip height, ``(B, 1)``.  The joint-angle cumsum is a matmul
    with an upper-triangular ones matrix built from iotas: Mosaic has no
    cumsum, and the kernel body may capture no constant arrays."""
    J = q.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (J, J), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (J, J), 1))
    cum = jnp.dot(q, tri.astype(q.dtype), precision=_HI,
                  preferred_element_type=jnp.float32)
    return root_z + jnp.sum(lengths * jnp.cos(cum), axis=1, keepdims=True)


def _step_core(q, qd, root, prev_action, t, seed, resets, action, sensor,
               tgt, masses, lengths, idx, *, chain, task, substeps, dt,
               max_episode_len):
    """Batched fused env step on (B, ...) arrays.

    Joint arrays are ``(B, J)``, ``root`` is ``(B, 6)``, per-env scalars
    (``t``, ``seed``, ``resets``) are ``(B, 1)`` columns, and
    ``tgt``/``masses``/``lengths`` are ``(1, J)`` rows: every value stays
    2-D, the only shapes the Mosaic lowering lays out without a reshape.
    Physics follows ``envs/physics.py::substep`` op for op (neighbor
    coupling via shifts instead of ``jnp.pad`` — same values, friendlier
    lowering), then reward/done/predicated-reset/observation.  Returns
    ``((q, qd, root, prev_action, t, resets), obs, reward, done)`` with
    ``obs`` observed AFTER any auto-reset, matching the vmap oracle, and
    ``reward``/``done`` as ``(B, 1)`` columns.
    """
    (damping, coupling, stiffness, max_qd, gravity, torque_scale,
     ground_k, ground_c) = chain
    w_fwd, w_up, w_ctrl, w_tgt, fall_z = task
    a = jnp.clip(action, -1.0, 1.0)
    inertia = masses * jnp.square(lengths) + 1e-3
    h = dt / substeps

    def body(_, carry):
        q, qd, root = carry
        left = jnp.concatenate([q[:, :1], q[:, :-1]], axis=1)
        right = jnp.concatenate([q[:, 1:], q[:, -1:]], axis=1)
        lap = left - 2.0 * q + right
        grav = gravity * masses * lengths * jnp.sin(q)
        qdd = (torque_scale * a - damping * qd - stiffness * q - grav
               + coupling * lap) / inertia
        qd = jnp.clip(qd + h * qdd, -max_qd, max_qd)
        q = q + h * qd
        tip_h = _tip_height(root[:, 2:3], q, lengths)
        pen = jnp.maximum(-tip_h, 0.0)
        contact_f = ground_k * pen - ground_c * jnp.minimum(
            root[:, 5:6], 0.0) * (pen > 0)
        thrust = jnp.concatenate([
            jnp.mean(jnp.sin(q) * a, axis=1, keepdims=True) * torque_scale,
            0.1 * jnp.mean(jnp.cos(2 * q) * a, axis=1, keepdims=True),
            contact_f - gravity * 0.5,
        ], axis=1)
        vel = (root[:, 3:] + h * thrust) * (1.0 - 0.02)
        pos = root[:, :3] + h * vel
        pos = jnp.concatenate(
            [pos[:, :2], jnp.maximum(pos[:, 2:3], 0.05)], axis=1)
        return q, qd, jnp.concatenate([pos, vel], axis=1)

    q, qd, root = jax.lax.fori_loop(0, substeps, body, (q, qd, root))
    upright = jnp.cos(jnp.mean(q, axis=1, keepdims=True))
    reward = (w_fwd * root[:, 3:4]
              + w_up * upright
              - w_ctrl * jnp.sum(jnp.square(a), axis=1, keepdims=True)
              - w_tgt * jnp.mean(jnp.square(q - tgt), axis=1, keepdims=True)
              + 0.5)                                     # alive bonus
    t = t + 1
    done = (t >= max_episode_len) | (root[:, 2:3] < fall_z)

    def do_reset(state):
        q, qd, root, pa, t, resets = state
        # fresh draws only materialize under the predicate — the whole
        # point of counter-based reset (same values as reset_fn)
        fresh_q = 0.1 * counter_normal(seed, resets + 1, idx)
        # suite reset root pose [0, 0, 0.6, 0, 0, 0] built via iota so the
        # kernel body captures no constant arrays
        cidx = jax.lax.broadcasted_iota(jnp.int32, root.shape, 1)
        root0 = jnp.where(cidx == 2, 0.6, 0.0).astype(root.dtype)
        return (jnp.where(done, fresh_q, q),
                jnp.where(done, 0.0, qd),
                jnp.where(done, root0, root),
                jnp.where(done, 0.0, pa),
                jnp.where(done, 0, t),
                jnp.where(done, resets + 1, resets))

    q, qd, root, pa, t, resets = jax.lax.cond(
        jnp.any(done), do_reset, lambda s: s, (q, qd, root, a, t, resets))

    raw = jnp.concatenate([
        root,
        jnp.sin(q), jnp.cos(q), qd,
        pa,
        _tip_height(root[:, 2:3], q, lengths), root[:, 2:3] - 0.6,
        jnp.mean(jnp.abs(qd), axis=1, keepdims=True),
    ], axis=1)
    obs = jnp.tanh(jnp.dot(raw, sensor, precision=_HI,
                           preferred_element_type=jnp.float32))
    return (q, qd, root, pa, t, resets), obs, reward, done


def _xla_step(q, qd, root, prev_action, t, seed, resets, action, sensor,
              tgt, masses, lengths, *, chain, task, substeps, dt,
              max_episode_len):
    """``_step_core`` on the public (N,)-scalar layout, in XLA.  Returns
    ``(q, qd, root, prev_action, t, seed, resets, obs, reward, done_f32)``
    with per-env scalars back as (N,) vectors."""
    col = lambda x: x.reshape(-1, 1)
    row = lambda x: x.reshape(1, -1)
    idx = jnp.arange(q.shape[1], dtype=jnp.uint32)[None, :]
    (q, qd, root, pa, t2, resets2), obs, reward, done = _step_core(
        q, qd, root, prev_action, col(t), col(seed), col(resets), action,
        sensor, row(tgt), row(masses), row(lengths), idx, chain=chain,
        task=task, substeps=substeps, dt=dt,
        max_episode_len=max_episode_len)
    return (q, qd, root, pa, t2.reshape(-1), seed, resets2.reshape(-1), obs,
            reward.reshape(-1), done.astype(jnp.float32).reshape(-1))


@functools.partial(jax.jit, static_argnames=("chain", "task", "substeps",
                                             "dt", "max_episode_len"))
def mega_step(q, qd, root, prev_action, t, seed, resets, action, sensor,
              tgt, masses, lengths, *, chain, task, substeps, dt,
              max_episode_len):
    """Fused XLA env step (no ring write): one jitted dispatch for
    physics + reward + bookkeeping + predicated reset + observation.
    Returns ``(q, qd, root, prev_action, t, seed, resets, obs, reward,
    done)`` with ``done`` a bool vector."""
    out = _xla_step(q, qd, root, prev_action, t, seed, resets, action,
                    sensor, tgt, masses, lengths, chain=chain, task=task,
                    substeps=substeps, dt=dt,
                    max_episode_len=max_episode_len)
    return out[:9] + (out[9] > 0,)


def _write_scalar_rows(bufs, reward, done_f, step_t, slot):
    """Land one step's reward/done rows in the ``(T, S*N)`` rings."""
    N = reward.shape[-1]
    at = (jnp.asarray(step_t, jnp.int32), jnp.asarray(slot, jnp.int32) * N)
    bufs = dict(bufs)
    bufs["rewards"] = jax.lax.dynamic_update_slice(
        bufs["rewards"], reward.reshape(1, N), at)
    bufs["dones"] = jax.lax.dynamic_update_slice(
        bufs["dones"], done_f.reshape(1, N), at)
    return bufs


def _mega_kernel(ts_ref, q_ref, qd_ref, root_ref, pa_ref, t_ref, seed_ref,
                 resets_ref, act_ref, obs_ref, sensor_ref, tgt_ref, m_ref,
                 l_ref, obuf_i, abuf_i,
                 q_o, qd_o, root_o, pa_o, t_o, resets_o, obs_o, rew_o,
                 done_o, obuf_o, abuf_o, *, chain, task, substeps, dt,
                 max_episode_len):
    del ts_ref, obuf_i, abuf_i        # slot rows addressed by the out specs
    # experience write (the obs the policy acted on + the raw action)
    obuf_o[...] = obs_ref[...]
    abuf_o[...] = act_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, q_ref.shape, 1).astype(
        jnp.uint32)
    (q, qd, root, pa, t, resets), obs, reward, done = _step_core(
        q_ref[...], qd_ref[...], root_ref[...], pa_ref[...], t_ref[...].T,
        seed_ref[...].T, resets_ref[...].T, act_ref[...], sensor_ref[...],
        tgt_ref[...], m_ref[...], l_ref[...], idx, chain=chain, task=task,
        substeps=substeps, dt=dt, max_episode_len=max_episode_len)
    q_o[...] = q
    qd_o[...] = qd
    root_o[...] = root
    pa_o[...] = pa
    t_o[...] = t.T
    resets_o[...] = resets.T
    obs_o[...] = obs
    rew_o[...] = reward.T
    done_o[...] = done.astype(jnp.float32).T


def env_mega_step(q, qd, root, prev_action, t, seed, resets, action, obs,
                  bufs, step_t, slot, sensor, tgt, masses, lengths, *,
                  chain, task, substeps, dt, max_episode_len,
                  block_envs=None, interpret: bool = False):
    """One fused env step over all N envs, grid over env blocks, writing
    the experience row straight into the ring slot (see module docstring).

    ``bufs`` is the ``{obs, actions, rewards, dones}`` subset of a
    ``ChannelRing`` allocation; the obs/action rings are aliased input ->
    output so untouched slots/rows survive.  Returns the ``mega_step``
    tuple (``done`` as float32) followed by the updated ring dict."""
    N, J = q.shape
    O, A = obs.shape[1], action.shape[1]
    be = block_envs or min(N, 256)
    assert N % be == 0, (N, be)
    nb = N // be
    ts = jnp.stack([jnp.asarray(step_t, jnp.int32),
                    jnp.asarray(slot, jnp.int32)])

    def blk(w):
        return pl.BlockSpec((be, w), lambda i, ts: (i, 0))

    def row():
        return pl.BlockSpec((1, be), lambda i, ts: (0, i))

    def full(shape):
        return pl.BlockSpec(shape, lambda i, ts: (0, 0))

    def ring_rows(w):
        return pl.BlockSpec((None, be, w),
                            lambda i, ts: (ts[0], ts[1] * nb + i, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[blk(J), blk(J), blk(6), blk(J), row(), row(), row(),
                  blk(A), blk(O), full(sensor.shape), full((1, J)),
                  full((1, J)), full((1, J)), hbm, hbm],
        out_specs=[blk(J), blk(J), blk(6), blk(J), row(), row(), blk(O),
                   row(), row(), ring_rows(O), ring_rows(A)],
    )
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                 for x in (q, qd, root, prev_action)]
    out_shape += [jax.ShapeDtypeStruct((1, N), jnp.int32)] * 2
    out_shape += [jax.ShapeDtypeStruct(obs.shape, obs.dtype)]
    out_shape += [jax.ShapeDtypeStruct((1, N), f32)] * 2
    out_shape += [jax.ShapeDtypeStruct(bufs[c].shape, bufs[c].dtype)
                  for c in ("obs", "actions")]
    row_of = lambda x: x.reshape(1, -1)
    # alias indices count the scalar-prefetch operand: ring inputs sit at
    # 14..15 (ts + 13 arrays ahead of them), ring outputs at 9..10
    q, qd, root, pa, t2, resets2, obs2, reward, done, obuf, abuf = \
        pl.pallas_call(
            functools.partial(_mega_kernel, chain=chain, task=task,
                              substeps=substeps, dt=dt,
                              max_episode_len=max_episode_len),
            grid_spec=grid_spec,
            out_shape=out_shape,
            input_output_aliases={14: 9, 15: 10},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(ts, q, qd, root, prev_action, row_of(t), row_of(seed),
          row_of(resets), action, obs, sensor, row_of(tgt),
          row_of(masses), row_of(lengths), bufs["obs"], bufs["actions"])
    reward, done = reward.reshape(N), done.reshape(N)
    rings = _write_scalar_rows(dict(bufs, obs=obuf, actions=abuf), reward,
                               done, step_t, slot)
    return (q, qd, root, pa, t2.reshape(N), seed, resets2.reshape(N), obs2,
            reward, done, rings)


def mega_step_ring(q, qd, root, prev_action, t, seed, resets, action,
                   obs, bufs, step_t, slot, sensor, tgt, masses,
                   lengths, *, chain, task, substeps, dt,
                   max_episode_len):
    """The identically fused XLA program (un-jitted, scan-composable):
    the ``_step_core`` step + dynamic-update-slice ring writes.  Same
    signature/contract as :func:`env_mega_step`; the off-TPU producer
    path, called inside ``rl.rollout.collect_ring``'s jitted scan."""
    N = q.shape[0]
    out = _xla_step(q, qd, root, prev_action, t, seed, resets, action,
                    sensor, tgt, masses, lengths, chain=chain, task=task,
                    substeps=substeps, dt=dt,
                    max_episode_len=max_episode_len)
    at = (jnp.asarray(step_t, jnp.int32),
          jnp.asarray(slot, jnp.int32) * N, jnp.int32(0))
    bufs = dict(bufs)
    bufs["obs"] = jax.lax.dynamic_update_slice(bufs["obs"], obs[None], at)
    bufs["actions"] = jax.lax.dynamic_update_slice(bufs["actions"],
                                                   action[None], at)
    return out + (_write_scalar_rows(bufs, out[8], out[9], step_t, slot),)
