"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=None, softcap=None,
                  scale=None, lengths=None):
    """q: (B, Sq, H, hd); k: (B, Skv, KH, hd); v: (B, Skv, KH, dv) with
    H % KH == 0.  ``lengths`` (B,): real positions of each right-padded
    row; keys past it are masked and query rows past it are zero."""
    B, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale if scale is not None else hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KH, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((1, Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if lengths is not None:
        mask &= kpos < lengths[:, None, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if lengths is not None:
        p = jnp.where((qpos < lengths[:, None, None])[:, None, None], p, 0.0)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, slot_pos, table, positions, *,
                        window=None, softcap=None, scale=None):
    """Gather-decode oracle for ``paged_decode.paged_decode_attention``.

    q: (B, H, hd) one decode token per row; k/v pages: (N, page, KH, hd);
    slot_pos: (N, page) absolute positions (-1 empty); table: (B, M)
    physical page ids (-1 unmapped -> masked); positions: (B,) absolute q
    position per row.  Gathers each row's pages into position order and
    runs plain masked softmax attention."""
    B, H, hd = q.shape
    N, page, KH, _ = k_pages.shape
    M = table.shape[1]
    G = H // KH
    scale = scale if scale is not None else hd ** -0.5
    tsafe = jnp.maximum(table, 0)
    k = k_pages[tsafe].reshape(B, M * page, KH, hd).astype(jnp.float32)
    v = v_pages[tsafe].reshape(B, M * page, KH, hd).astype(jnp.float32)
    kpos = jnp.where(jnp.repeat(table >= 0, page, axis=1),
                     slot_pos[tsafe].reshape(B, M * page), -1)
    qf = (q.astype(jnp.float32) * scale).reshape(B, KH, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    qpos = positions[:, None]
    valid = (kpos >= 0) & (kpos <= qpos)
    if window is not None:
        valid &= kpos > qpos - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # zero invalid v rows: garbage pool values must not leak through the
    # uniform-softmax degrade of fully-masked rows
    o = jnp.einsum("bkgs,bskd->bkgd", p,
                   jnp.where(valid[:, :, None, None], v, 0.0))
    return o.reshape(B, H, hd).astype(q.dtype)


def policy_mlp_ref(x, weights, biases):
    """x: (N, in); tanh MLP trunk: h = tanh(h @ w + b) per layer."""
    h = x.astype(jnp.float32)
    for w, b in zip(weights, biases):
        h = jnp.tanh(h @ w.astype(jnp.float32) + b.astype(jnp.float32))
    return h.astype(x.dtype)


def gae_norm_ref(rewards, values, dones, last_value, gamma: float = 0.99,
                 lam: float = 0.95, eps: float = 1e-8):
    """Fused-GAE oracle: reverse scan + global advantage normalization.

    rewards/values/dones: (T, N); last_value: (N,).  Returns
    (normalized_advs, returns), both (T, N) float32."""
    r = rewards.astype(jnp.float32)
    v = values.astype(jnp.float32)
    d = dones.astype(jnp.float32)
    last = last_value.astype(jnp.float32)

    def step(carry, xs):
        adv_next, v_next = carry
        rt, vt, dt = xs
        nonterm = 1.0 - dt
        delta = rt + gamma * v_next * nonterm - vt
        adv = delta + gamma * lam * nonterm * adv_next
        return (adv, vt), adv

    (_, _), advs = jax.lax.scan(step, (jnp.zeros_like(last), last),
                                (r, v, d), reverse=True)
    returns = advs + v
    advs = (advs - advs.mean()) / (advs.std() + eps)
    return advs, returns


def nstep_returns_ref(rewards, dones, bootstrap, gamma: float = 0.99):
    """Fused n-step-return oracle: reverse discounted scan bootstrapped
    from the last value.  rewards/dones: (T, N); bootstrap: (N,).
    Returns (T, N) float32."""
    r = rewards.astype(jnp.float32)
    d = dones.astype(jnp.float32)

    def step(carry, xs):
        rt, dt = xs
        g = rt + gamma * carry * (1.0 - dt)
        return g, g

    _, rets = jax.lax.scan(step, bootstrap.astype(jnp.float32), (r, d),
                           reverse=True)
    return rets


def pack_channels_ref(bufs, payloads, slot):
    """Ring-pack oracle via functional .at[] updates (same layout as
    ``channel_pack``: slot-aligned columns / rows)."""
    T, N = payloads["rewards"].shape
    col = slot * N
    boot = jnp.asarray(payloads["bootstrap"]).reshape(1, N)
    ver = jnp.asarray(payloads["actor_version"], jnp.int32).reshape(1, 1)
    return {
        "obs": bufs["obs"].at[:, col:col + N, :].set(payloads["obs"]),
        "actions": bufs["actions"].at[:, col:col + N, :].set(
            payloads["actions"]),
        "rewards": bufs["rewards"].at[:, col:col + N].set(
            payloads["rewards"]),
        "dones": bufs["dones"].at[:, col:col + N].set(payloads["dones"]),
        "bootstrap": bufs["bootstrap"].at[slot:slot + 1, :].set(boot),
        "actor_version": bufs["actor_version"].at[slot:slot + 1, :].set(ver),
    }


def env_mega_step_ref(q, qd, root, prev_action, t, seed, resets, action,
                      obs, bufs, step_t, slot, sensor, tgt, masses,
                      lengths, *, chain, task, substeps, dt,
                      max_episode_len):
    """Env-megakernel oracle: the *vmapped per-env* composition of
    ``envs/physics.py::rollout_substeps`` + suite reward/bookkeeping with
    a MATERIALIZED counter-based auto-reset (fresh state computed for
    every env, selected by ``jnp.where``), plus functional ``.at[]`` ring
    writes in the ``channel_pack`` slot layout.  ``step_t``/``slot`` are
    concrete ints here.  Returns the ``env_mega_step`` tuple:
    ``(q, qd, root, prev_action, t, seed, resets, obs, reward, done_f32,
    bufs)``."""
    from repro.envs.physics import (ChainParams, counter_normal,
                                    rollout_substeps, tip_height)
    params = ChainParams(masses, lengths, *chain)
    w_fwd, w_up, w_ctrl, w_tgt, fall_z = task
    J = q.shape[1]
    root0 = jnp.array([0., 0., 0.6, 0., 0., 0.])

    def one(q, qd, root, pa, t, seed, resets, a_raw):
        a = jnp.clip(a_raw, -1.0, 1.0)
        q, qd, root = rollout_substeps(q, qd, root, a, params, dt, substeps)
        reward = (w_fwd * root[3]
                  + w_up * jnp.cos(jnp.mean(q))
                  - w_ctrl * jnp.sum(jnp.square(a))
                  - w_tgt * jnp.mean(jnp.square(q - tgt))
                  + 0.5)
        t = t + 1
        done = (t >= max_episode_len) | (root[2] < fall_z)
        fresh_q = 0.1 * counter_normal(seed, resets + 1,
                                       jnp.arange(J, dtype=jnp.uint32))
        q = jnp.where(done, fresh_q, q)
        qd = jnp.where(done, 0.0, qd)
        root = jnp.where(done, root0, root)
        pa = jnp.where(done, 0.0, a)
        t = jnp.where(done, 0, t)
        resets = jnp.where(done, resets + 1, resets)
        tip = tip_height(q, root[2], params)
        raw = jnp.concatenate([
            root, jnp.sin(q), jnp.cos(q), qd, pa,
            jnp.array([tip, root[2] - 0.6, jnp.mean(jnp.abs(qd))]),
        ])
        return q, qd, root, pa, t, resets, jnp.tanh(raw @ sensor), \
            reward, done

    q, qd, root, pa, t, resets, obs2, reward, done = jax.vmap(one)(
        q, qd, root, prev_action, t, seed, resets, action)
    N = q.shape[0]
    col = slot * N
    done_f = done.astype(jnp.float32)
    bufs = {
        "obs": bufs["obs"].at[step_t, col:col + N, :].set(obs),
        "actions": bufs["actions"].at[step_t, col:col + N, :].set(action),
        "rewards": bufs["rewards"].at[step_t, col:col + N].set(reward),
        "dones": bufs["dones"].at[step_t, col:col + N].set(done_f),
    }
    return (q, qd, root, pa, t, seed, resets, obs2, reward, done_f, bufs)


def mlstm_chunkwise_ref(q, k, v, log_i, log_f, chunk: int = 64):
    """q/k/v: (B, H, S, dh); log_i/log_f: (B, H, S).  Chunkwise-parallel
    stabilized mLSTM, zero initial state.  Returns h: (B, H, S, dh)."""
    from repro.models.ssm import _mlstm_chunk
    B, H, S, dh = q.shape
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    C = jnp.zeros((B, H, dh, dh), jnp.float32)
    n = jnp.zeros((B, H, dh), jnp.float32)
    m = jnp.zeros((B, H), jnp.float32)
    outs = []
    for c in range(nc):
        sl = slice(c * L, (c + 1) * L)
        h, C, n, m = _mlstm_chunk(
            q[:, :, sl].astype(jnp.float32), k[:, :, sl].astype(jnp.float32),
            v[:, :, sl].astype(jnp.float32), log_i[:, :, sl], log_f[:, :, sl],
            C, n, m)
        outs.append(h)
    return jnp.concatenate(outs, axis=2).astype(q.dtype)


def gmm_ref(lhs, rhs, group_sizes):
    """Grouped-matmul oracle: each row times the matrix of the group it
    falls in (groups are consecutive row ranges of ``group_sizes``); rows
    past ``sum(group_sizes)`` are zero.  float32 at HIGHEST."""
    rows = jnp.arange(lhs.shape[0])
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, rows, side="right")
    held = rows < ends[-1]
    w = rhs[jnp.minimum(group, rhs.shape[0] - 1)]          # (M, K, N)
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32),
                     w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.where(held[:, None], out, 0.0)
