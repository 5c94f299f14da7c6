"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute with ``interpret=True`` — the
kernel body runs in Python per grid cell, validating the exact TPU program
against the ``ref.py`` oracles.  On TPU the same calls compile to Mosaic.
Any other backend is an error: there is no silent interpret fallback.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.channel_pack import pack_channels as _pack
from repro.kernels.env_megakernel import env_mega_step as _envmega
from repro.kernels.flash_attention import flash_attention as _fa
from repro.kernels.fused_policy_mlp import fused_policy_mlp as _mlp
from repro.kernels.gae_scan import gae_scan as _gae
from repro.kernels.gae_scan import nstep_scan as _nstep
from repro.kernels.mlstm_scan import mlstm_chunkwise as _mlstm
from repro.kernels.paged_decode import paged_decode_attention as _paged


def _interpret_default() -> bool:
    """True on the CPU backend, False on TPU; raises anywhere else."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels here target TPU (compiled) or CPU "
                       f"(interpret mode); backend {backend!r} is neither")


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def attention(q, k, v, *, causal=True, window=None, softcap=None,
              block_q=128, block_k=128, interpret=None):
    interp = _interpret_default() if interpret is None else interpret
    return _fa(q, k, v, causal=causal, window=window, softcap=softcap,
               block_q=block_q, block_k=block_k, interpret=interp)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_attention(q, k_pages, v_pages, slot_pos, table, positions, *,
                    window=None, softcap=None, scale=None, interpret=None):
    """Paged gather-decode attention (see paged_decode.py): one decode
    step per batch row read through a per-row page table.  ``window`` is a
    dynamic operand (it rides the kernel's scalar prefetch), so per-layer
    windows from a scanned stack don't retrace."""
    interp = _interpret_default() if interpret is None else interpret
    return _paged(q, k_pages, v_pages, slot_pos, table, positions,
                  window=window, softcap=softcap, scale=scale,
                  interpret=interp)


def policy_mlp(x, weights, biases, *, block_n=256, interpret=None):
    interp = _interpret_default() if interpret is None else interpret
    fn = jax.jit(functools.partial(_mlp, block_n=block_n, interpret=interp))
    return fn(x, list(weights), list(biases))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm(q, k, v, log_i, log_f, *, chunk=128, interpret=None):
    interp = _interpret_default() if interpret is None else interpret
    return _mlstm(q, k, v, log_i, log_f, chunk=chunk, interpret=interp)


@functools.partial(jax.jit,
                   static_argnames=("gamma", "lam", "eps", "interpret"))
def gae_norm(rewards, values, dones, last_value, *, gamma=0.99, lam=0.95,
             eps=1e-8, interpret=None):
    """Fused GAE + global advantage normalization (see gae_scan.py).

    Returns (normalized_advs, returns), both (T, N) f32."""
    interp = _interpret_default() if interpret is None else interpret
    return _gae(rewards, values, dones, last_value, gamma=gamma, lam=lam,
                eps=eps, interpret=interp)


@functools.partial(jax.jit, static_argnames=("gamma", "interpret"))
def nstep_returns(rewards, dones, bootstrap, *, gamma=0.99, interpret=None):
    """Fused A3C n-step discounted-return scan (see gae_scan.nstep_scan).

    Returns the (T, N) f32 return block."""
    interp = _interpret_default() if interpret is None else interpret
    return _nstep(rewards, dones, bootstrap, gamma=gamma, interpret=interp)


@functools.partial(jax.jit, donate_argnums=(9,),
                   static_argnames=("chain", "task", "substeps", "dt",
                                    "max_episode_len", "block_envs",
                                    "interpret"))
def env_mega_step(q, qd, root, prev_action, t, seed, resets, action, obs,
                  bufs, step_t, slot, sensor, tgt, masses, lengths, *,
                  chain, task, substeps, dt, max_episode_len,
                  block_envs=None, interpret=None):
    """Fused env megakernel step (see env_megakernel.py): physics
    substeps + reward + bookkeeping + predicated counter-PRNG auto-reset
    + observation, writing obs/action/reward/done straight into the
    donated ring-slot buffers.  Returns the new state arrays, next obs,
    reward, done, and the updated ring dict."""
    interp = _interpret_default() if interpret is None else interpret
    return _envmega(q, qd, root, prev_action, t, seed, resets, action,
                    obs, bufs, step_t, slot, sensor, tgt, masses, lengths,
                    chain=chain, task=task, substeps=substeps, dt=dt,
                    max_episode_len=max_episode_len, block_envs=block_envs,
                    interpret=interp)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("interpret",))
def pack_channels(bufs, payloads, slot, *, interpret=None):
    """In-place ring-buffer pack of one experience push (all channels in
    one kernel launch; ring buffers donated)."""
    interp = _interpret_default() if interpret is None else interpret
    return _pack(bufs, payloads, slot, interpret=interp)
