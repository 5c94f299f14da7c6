"""Fused GAE Pallas kernel with global advantage normalization, and its
A3C sibling: the fused n-step discounted-return reverse scan
(:func:`nstep_scan`).

The PPO hot path runs generalized advantage estimation as an unfused
``lax.scan`` followed by a separate returns pass and a mean/std
normalization.  The kernel computes, per env tile resident in VMEM:

  1. reverse scan  adv_t = delta_t + gamma*lam*(1-d_t) * adv_{t+1}
  2. returns_t     = adv_t + v_t

and the jitted wrapper then normalizes ``(advs - mean) / (std + eps)``
over the full T*N block in one XLA epilogue (a global reduction across
tiles).

The grid tiles the env (lane) axis: each step holds a ``(T, block_n)``
column block, so VMEM use is independent of N.  A whole-batch block does
not fit the chip's scoped VMEM at the env counts of the paper's
num_env sweep (65536 envs x T=16 needs 24 MiB against a 16 MiB limit).

Numerics note: normalizing once over the whole batch (not per minibatch)
is the standard large-batch PPO formulation; the unfused path keeps the
per-minibatch normalization, so the two paths are shape-compatible but not
bit-identical — by design.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_BUDGET = 8 << 20      # bytes of double-buffered blocks per grid step


def _lane_block(T: int, N: int, arrays: int) -> int:
    """Env-axis tile for ``arrays`` (T, N) f32 blocks: the widest multiple
    of 128 dividing N whose double-buffered blocks fit ``_VMEM_BUDGET``;
    N itself when N is not a multiple of 128 (one whole block)."""
    if N % 128:
        return N
    for bn in (4096, 2048, 1024, 512, 256):
        if N % bn == 0 and 2 * arrays * T * bn * 4 <= _VMEM_BUDGET:
            return bn
    return 128


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _kernel(r_ref, v_ref, d_ref, last_ref, adv_ref, ret_ref, *,
            gamma: float, lam: float):
    T = r_ref.shape[0]
    last = last_ref[...]                              # (1, N)

    def step(i, carry):
        adv, v_next = carry
        t = T - 1 - i
        r = r_ref[pl.ds(t, 1), :]
        v = v_ref[pl.ds(t, 1), :]
        nonterm = 1.0 - d_ref[pl.ds(t, 1), :]
        delta = r + gamma * v_next * nonterm - v
        adv = delta + gamma * lam * nonterm * adv
        adv_ref[pl.ds(t, 1), :] = adv
        ret_ref[pl.ds(t, 1), :] = adv + v
        return (adv, v)

    jax.lax.fori_loop(0, T, step, (jnp.zeros_like(last), last))


def gae_scan(rewards, values, dones, last_value, *, gamma: float = 0.99,
             lam: float = 0.95, eps: float = 1e-8,
             interpret: bool = False):
    """rewards/values/dones: (T, N); last_value: (N,).

    Returns (normalized_advantages, returns), both (T, N) float32.
    """
    T, N = rewards.shape
    f32 = jnp.float32
    last = jnp.asarray(last_value, f32).reshape(1, N)
    bn = _lane_block(T, N, 5)
    tile = pl.BlockSpec((T, bn), lambda i: (0, i))
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    advs, rets = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, lam=lam),
        grid=(N // bn,),
        in_specs=[tile] * 3 + [row],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((T, N), f32)] * 2,
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(rewards.astype(f32), values.astype(f32), dones.astype(f32), last)
    return (advs - jnp.mean(advs)) / (jnp.std(advs) + eps), rets


# ------------------------------------------------------ A3C n-step scan ----
def _nstep_kernel(r_ref, d_ref, boot_ref, ret_ref, *, gamma: float):
    T = r_ref.shape[0]

    def step(i, carry):
        t = T - 1 - i
        r = r_ref[pl.ds(t, 1), :]
        nonterm = 1.0 - d_ref[pl.ds(t, 1), :]
        g = r + gamma * carry * nonterm
        ret_ref[pl.ds(t, 1), :] = g
        return g

    jax.lax.fori_loop(0, T, step, boot_ref[...])


def nstep_scan(rewards, dones, bootstrap, *, gamma: float = 0.99,
               interpret: bool = False):
    """Fused A3C n-step discounted returns: each (T, block_n) env tile
    stays in VMEM for the reverse scan
    ``G_t = r_t + gamma * (1 - d_t) * G_{t+1}`` bootstrapped from the
    actor's last value estimate.

    rewards/dones: (T, N); bootstrap: (N,).  Returns (T, N) float32.
    """
    T, N = rewards.shape
    f32 = jnp.float32
    boot = jnp.asarray(bootstrap, f32).reshape(1, N)
    bn = _lane_block(T, N, 3)
    tile = pl.BlockSpec((T, bn), lambda i: (0, i))
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_nstep_kernel, gamma=gamma),
        grid=(N // bn,),
        in_specs=[tile, tile, row],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((T, N), f32),
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(rewards.astype(f32), dones.astype(f32), boot)
