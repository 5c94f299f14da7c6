"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute with ``interpret=True`` — the
kernel body runs in Python per grid cell, validating the exact TPU program
against the ``ref.py`` oracles.  On TPU the same calls compile to Mosaic.
Any other backend is an error: there is no silent interpret fallback.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

from repro.kernels.channel_pack import pack_channels as _pack
from repro.kernels.env_megakernel import env_mega_step as _envmega
from repro.kernels.flash_attention import flash_attention as _fa
from repro.kernels.fused_policy_mlp import fused_policy_mlp as _mlp
from repro.kernels.gae_scan import gae_scan as _gae
from repro.kernels.gae_scan import nstep_scan as _nstep
from repro.kernels.mlstm_scan import mlstm_chunkwise as _mlstm
from repro.kernels.paged_decode import paged_decode_attention as _paged

# the kernels' module (the package exports its custom-VJP ``gmm`` under
# the module's name)
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")


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
                                             "dot_dtype", "block_q",
                                             "block_k", "interpret"))
def attention(q, k, v, lengths=None, *, causal=True, window=None,
              softcap=None, dot_dtype=None, block_q=128, block_k=128,
              interpret=None):
    """Block-skipping flash attention (see flash_attention.py), with a
    custom VJP.  ``lengths`` (B,): real positions per right-padded row;
    rows past it come back zero.  ``dot_dtype``: the products' operand
    dtype (``None``: q's).  The kernels' instructions are named
    ``attention`` (forward), ``attention_dq`` and ``attention_dkv``."""
    interp = _interpret_default() if interpret is None else interpret
    return _fa(q, k, v, lengths, causal=causal, window=window,
               softcap=softcap, dot_dtype=dot_dtype, block_q=block_q,
               block_k=block_k, interpret=interp)


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


def _gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn): 512-row tiles, the whole output width where it is
    at most 2048 lanes (so a row block is read once), else 512/256/128."""
    def fit(d, widest):
        for t in (widest, 512, 256, 128):
            if t <= d and d % t == 0 and t % 128 == 0:
                return t
        return d
    return fit(m, 512), fit(k, 512), fit(n, n if n <= 2048 else 512)


def _rows_held(x, group_sizes):
    """Rows past ``sum(group_sizes)`` are never written by the kernel."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(rows < jnp.sum(group_sizes), x, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, dtypes, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, dtypes, interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, dtypes, interpret):
    xb, wb = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    out = _megablox.gmm(xb, wb, group_sizes, jnp.float32,
                        _gmm_tiling(lhs.shape[0], lhs.shape[1],
                                    rhs.shape[2]), interpret=interpret)
    return _rows_held(out, group_sizes), (xb, wb, group_sizes)


def _gmm_bwd(dtypes, interpret, res, dy):
    xb, wb, group_sizes = res
    m, k, n = xb.shape[0], xb.shape[1], wb.shape[2]
    dyb = dy.astype(jnp.bfloat16)
    dx = _megablox.gmm(dyb, wb, group_sizes, jnp.float32,
                       _gmm_tiling(m, n, k), transpose_rhs=True,
                       interpret=interpret)
    dw = _megablox.tgmm(xb.T, dyb, group_sizes, jnp.float32,
                        _gmm_tiling(m, k, n), interpret=interpret)
    return (_rows_held(dx, group_sizes).astype(dtypes[0]),
            dw.astype(dtypes[1]), None)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs, rhs, group_sizes, *, interpret=None):
    """Grouped matrix product (Pallas megablox ``gmm``, weight gradient by
    its ``tgmm``): rows ``[o_g, o_g + group_sizes[g])`` of ``lhs`` (M, K),
    with ``o_g`` the sizes before ``g``, times ``rhs[g]`` (K, N).  Rows
    past ``sum(group_sizes)`` come back zero.  Operands enter the MXU as
    bf16 and accumulate in f32, as a float32 product does at the TPU's
    default precision; the result is float32.  M must be a multiple of
    128 (or under it)."""
    interp = _interpret_default() if interpret is None else interpret
    dtypes = (jnp.dtype(lhs.dtype), jnp.dtype(rhs.dtype))
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), dtypes, interp)
