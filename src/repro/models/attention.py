"""GQA attention with sliding-window, logit softcap, QKV-bias, KV caches;
and latent attention (MLA) for a training pass (:func:`mla`).

Two execution paths:
  * ``direct``  — materializes (…, Sq, Skv) scores; used for small sequences
    and as the oracle.
  * ``chunked`` — flash-style double-blocked online softmax expressed with
    ``jax.lax.scan`` (O(block²) live scores); used for long sequences so the
    32k/500k dry-run shapes fit HBM.  The Pallas kernel in
    ``repro.kernels.flash_attention`` is the TPU-tiled version of the same
    algorithm.

Caches:
  * full cache  — (B, S, n_kv, hd) k/v with write index = absolute position.
  * ring cache  — (B, W, n_kv, hd) sliding-window ring buffer plus a
    ``slot_pos`` (B, W) absolute-position map, for ``long_500k`` decode.
  * paged cache — a batch-free pool of fixed-size pages
    (num_pages, page, n_kv, hd) addressed through a per-request page table
    (B, M): virtual page v of a request holds absolute positions
    ``[v*page, (v+1)*page)`` regardless of any sliding window (the window
    applies purely through ``_mask``), so a gathered table row reproduces
    the full-depth cache layout exactly.  Page 0 is the trash page: writes
    from idle rows and unmapped virtual pages land there and stay masked
    (its ``slot_pos`` is only ever written -1).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, init_linear, linear,
                                 rms_norm, softcap)

NEG_INF = -1e30


class KVCache(NamedTuple):
    """KV cache; ring-buffer and linear caches are unified: writes always go
    to slot ``pos % W`` and masking always reads absolute positions from
    ``slot_pos`` (for a full-length cache pos % W == pos)."""
    k: jax.Array          # (B, S_or_W, n_kv, hd)
    v: jax.Array
    slot_pos: jax.Array   # (B, S_or_W) absolute position in each slot (-1 empty)


class PagedKVCache(NamedTuple):
    """Paged KV cache: a shared physical pool of fixed-size pages plus the
    absolute position each page slot holds.  Batch-free — requests address
    it through a page table (B, M) owned by the serving engine."""
    k_pages: jax.Array     # (num_pages, page, n_kv, hd)
    v_pages: jax.Array
    slot_pos: jax.Array    # (num_pages, page) absolute position (-1 empty)


def init_attention_params(key, d_model: int, num_heads: int, num_kv_heads: int,
                          head_dim: int, qkv_bias: bool = False):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_linear(kq, d_model, num_heads * head_dim, qkv_bias),
        "wk": init_linear(kk, d_model, num_kv_heads * head_dim, qkv_bias),
        "wv": init_linear(kv, d_model, num_kv_heads * head_dim, qkv_bias),
        "wo": init_linear(ko, num_heads * head_dim, d_model, False),
    }


def make_cache(batch: int, seq: int, n_kv: int, head_dim: int,
               window: Optional[int] = None, dtype=jnp.float32) -> KVCache:
    size = min(seq, window) if window else seq
    return KVCache(
        k=jnp.zeros((batch, size, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, size, n_kv, head_dim), dtype),
        slot_pos=jnp.full((batch, size), -1, jnp.int32),
    )


def make_paged_cache(num_pages: int, page: int, n_kv: int, head_dim: int,
                     dtype=jnp.float32) -> PagedKVCache:
    return PagedKVCache(
        k_pages=jnp.zeros((num_pages, page, n_kv, head_dim), dtype),
        v_pages=jnp.zeros((num_pages, page, n_kv, head_dim), dtype),
        slot_pos=jnp.full((num_pages, page), -1, jnp.int32),
    )


def paged_write(cache: PagedKVCache, page_table, positions, k, v):
    """Scatter k/v (B, S, KH, hd) at absolute ``positions`` (B, S) into the
    pool through ``page_table`` (B, M).  Negative positions and unmapped
    virtual pages route to the trash page 0 with slot_pos -1."""
    P = cache.k_pages.shape[1]
    M = page_table.shape[-1]
    ok = positions >= 0
    safe = jnp.where(ok, positions, 0)
    vp = jnp.clip(safe // P, 0, M - 1)
    off = safe % P
    phys = jnp.take_along_axis(page_table, vp, axis=1)       # (B, S)
    ok &= phys >= 0
    phys = jnp.where(ok, phys, 0)
    ck = cache.k_pages.at[phys, off].set(k.astype(cache.k_pages.dtype))
    cv = cache.v_pages.at[phys, off].set(v.astype(cache.v_pages.dtype))
    cp = cache.slot_pos.at[phys, off].set(jnp.where(ok, positions, -1))
    return PagedKVCache(ck, cv, cp)


def paged_gather(cache: PagedKVCache, page_table):
    """Gather each row's pages into position order: (B, M*page, KH, hd)
    k/v plus (B, M*page) kpos (-1 where the virtual page is unmapped).
    Row j of the gathered view is absolute position j, so it reproduces
    the dense full-depth cache layout exactly."""
    P = cache.k_pages.shape[1]
    B, M = page_table.shape
    tsafe = jnp.maximum(page_table, 0)
    KH, hd = cache.k_pages.shape[2], cache.k_pages.shape[3]
    k = cache.k_pages[tsafe].reshape(B, M * P, KH, hd)
    v = cache.v_pages[tsafe].reshape(B, M * P, KH, hd)
    kpos = jnp.where(jnp.repeat(page_table >= 0, P, axis=1),
                     cache.slot_pos[tsafe].reshape(B, M * P), -1)
    return k, v, kpos


# --------------------------------------------------------------------------
def _mask(qpos, kpos, causal: bool, window):
    """qpos: (..., Sq), kpos: (..., Skv) -> bool (..., Sq, Skv)."""
    q = qpos[..., :, None]
    k = kpos[..., None, :]
    valid = k >= 0
    if causal:
        valid &= k <= q
    if window is not None:
        valid &= k > q - window
    return valid


def _direct_attention(q, k, v, qpos, kpos, causal, window, cap, scale):
    """q: (B,Sq,H,hd)  k/v: (B,Skv,KH,hd).

    k/v stay in their storage dtype (casting a 32k-deep KV cache to f32
    costs GiBs of HBM per layer); the MXU accumulates in f32 via
    ``preferred_element_type``."""
    B, Sq, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    qf = (q * scale).astype(k.dtype).reshape(B, Sq, KH, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, k,
                        preferred_element_type=jnp.float32)
    scores = softcap(scores, cap)
    m = _mask(qpos, kpos, causal, window)              # (B?,Sq,Skv)
    m = m[:, None, None] if m.ndim == 3 else m[None, None, None]
    scores = jnp.where(m, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def _chunked_attention(q, k, v, qpos, kpos, causal, window, cap, scale,
                       q_block: int = 512, kv_block: int = 1024):
    """Flash-style blocked attention with online softmax (pure lax.scan).

    The value width ``dv`` may differ from the query/key width ``hd``
    (latent attention: 192-wide queries and keys, 128-wide values).  Each
    query block is rematerialized under a gradient, so a backward pass
    holds one block's scores at a time, not every block's."""
    B, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KH
    qb = min(q_block, Sq)
    kb = min(kv_block, Skv)
    nq = -(-Sq // qb)
    nk = -(-Skv // kb)
    pq = nq * qb - Sq
    pk = nk * kb - Skv
    # pad; padded key slots get kpos = -1 so the mask kills them
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qposp = jnp.pad(qpos, [(0, 0)] * (qpos.ndim - 1) + [(0, pq)])
    kposp = jnp.pad(kpos, [(0, 0)] * (kpos.ndim - 1) + [(0, pk)],
                    constant_values=-1)
    qp = qp.reshape(B, nq, qb, H, hd).transpose(1, 0, 2, 3, 4)
    kp = kp.reshape(B, nk, kb, KH, hd).transpose(1, 0, 2, 3, 4)
    vp = vp.reshape(B, nk, kb, KH, dv).transpose(1, 0, 2, 3, 4)
    qposp = jnp.broadcast_to(qposp, (B, nq * qb)).reshape(B, nq, qb).transpose(1, 0, 2)
    kposp = jnp.broadcast_to(kposp, (B, nk * kb)).reshape(B, nk, kb).transpose(1, 0, 2)

    @jax.checkpoint
    def q_step(_, qc):
        qi, qpi = qc                                    # (B,qb,H,hd), (B,qb)
        qf = (qi * scale).astype(k.dtype).reshape(B, qb, KH, G, hd)

        def kv_step(carry, kc):
            m_prev, l_prev, acc = carry
            ki, vi, kpi = kc
            s = jnp.einsum("bqkgd,bskd->bkgqs", qf, ki,
                           preferred_element_type=jnp.float32)
            s = softcap(s, cap)
            msk = _mask(qpi, kpi, causal, window)[:, None, None]
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        init = (jnp.full((B, KH, G, qb), NEG_INF, jnp.float32),
                jnp.zeros((B, KH, G, qb), jnp.float32),
                jnp.zeros((B, KH, G, qb, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init, (kp, vp, kposp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 3, 1, 2, 4).reshape(B, qb, H, dv)

    _, outs = jax.lax.scan(q_step, None, (qp, qposp))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * qb, H, dv)
    return out[:, :Sq].astype(q.dtype)


# --------------------------------------------------------------------------
def attention(params, x, *, num_heads: int, num_kv_heads: int, head_dim: int,
              positions, causal: bool = True, window: Optional[int] = None,
              attn_cap: Optional[float] = None, rope_theta: float = 10_000.0,
              cache: Optional[KVCache] = None,
              chunked_threshold: int = 4096,
              use_rope: bool = True,
              page_table=None, paged_kernel: bool = False):
    """Full attention block.  x: (B, S, D); positions: (B, S) or (S,).

    If ``cache`` is given and S == 1 this is a decode step: write k/v into the
    cache at ``positions`` and attend over the cache.  If cache is given with
    S > 1 (prefill) the cache is filled and returned.

    A :class:`PagedKVCache` requires ``page_table`` (B, M) and supports both
    S == 1 (paged decode: write the step's k/v through the table, attend
    over the gathered pages) and S > 1 (chunked prefill: write the whole
    chunk at absolute positions, then attend the chunk's queries over the
    gathered pages — the just-written in-chunk keys included, with the
    causal mask handling intra-chunk order).  ``paged_kernel=True`` routes
    the S == 1 paged read through the Pallas gather-decode kernel
    (``repro.kernels.paged_decode``) instead of the jnp gather.
    Returns (out, new_cache).
    """
    B, S, D = x.shape
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (B, S))
    q = linear(params["wq"], x).reshape(B, S, num_heads, head_dim)
    k = linear(params["wk"], x).reshape(B, S, num_kv_heads, head_dim)
    v = linear(params["wv"], x).reshape(B, S, num_kv_heads, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    scale = head_dim ** -0.5

    new_cache = cache
    if isinstance(cache, PagedKVCache):
        if page_table is None:
            raise ValueError("paged cache requires a page_table")
        new_cache = paged_write(cache, page_table, positions, k, v)
        if S == 1 and paged_kernel:
            from repro.kernels import ops
            o = ops.paged_attention(
                q[:, 0], new_cache.k_pages, new_cache.v_pages,
                new_cache.slot_pos, page_table, positions[:, 0],
                window=window, softcap=attn_cap, scale=scale)
            out = linear(params["wo"], o.reshape(B, 1, num_heads * head_dim))
            return out, new_cache
        k_all, v_all, kpos = paged_gather(new_cache, page_table)
    elif cache is not None and S == 1:
        # decode: write this step's k/v into its ring slot, attend over cache
        W = cache.k.shape[1]
        slots = positions % W                                # (B,1)
        bidx = jnp.arange(B)[:, None]
        ck = cache.k.at[bidx, slots].set(k.astype(cache.k.dtype))
        cv = cache.v.at[bidx, slots].set(v.astype(cache.v.dtype))
        cp = cache.slot_pos.at[bidx, slots].set(positions)
        new_cache = KVCache(ck, cv, cp)
        k_all, v_all, kpos = ck, cv, cp
    elif cache is not None:
        # prefill: attend over the fresh in-context k/v (a ring cache cannot
        # hold S > W simultaneous writes); persist only the last W positions,
        # which is exactly what windowed decode will ever read.
        W = cache.k.shape[1]
        n = min(S, W)
        k_tail, v_tail, p_tail = k[:, -n:], v[:, -n:], positions[:, -n:]
        slots = p_tail % W
        bidx = jnp.arange(B)[:, None]
        ck = cache.k.at[bidx, slots].set(k_tail.astype(cache.k.dtype))
        cv = cache.v.at[bidx, slots].set(v_tail.astype(cache.v.dtype))
        cp = cache.slot_pos.at[bidx, slots].set(p_tail)
        new_cache = KVCache(ck, cv, cp)
        k_all, v_all, kpos = k, v, positions
    else:
        k_all, v_all, kpos = k, v, positions

    Skv = k_all.shape[1]
    if max(S, Skv) > chunked_threshold and S > 1:
        out = _chunked_attention(q, k_all, v_all, positions, kpos,
                                 causal, window, attn_cap, scale)
    else:
        out = _direct_attention(q, k_all, v_all, positions, kpos,
                                causal, window, attn_cap, scale)
    out = linear(params["wo"], out.reshape(B, S, num_heads * head_dim))
    return out, new_cache


# --------------------------------------------------------------------------
def mla(params, x, positions, *, num_heads: int, qk_nope_dim: int,
        qk_rope_dim: int, v_dim: int, rope_theta: float, norm_eps: float,
        chunked_threshold: int = 4096):
    """Multi-head latent attention (DeepSeek-V2/V3) without a query LoRA,
    for a training pass: full keys and values are built from the latent.

    ``q = x W_q`` split per head into ``qk_nope_dim`` + ``qk_rope_dim``;
    ``[c, k_r] = x W_kva``, ``c`` RMS-normed; ``[k_nope, v] = c W_kvb``
    per head; one rotated ``k_r`` is shared by every head.  Causal scores
    ``(q_nope k_nope + q_r k_r) / sqrt(qk_nope_dim + qk_rope_dim)``; the
    ``v_dim``-wide head outputs go through ``W_o``.  Rope pairs use
    :func:`apply_rope`'s half-split layout.  x: (B, S, D); positions:
    (S,) or (B, S)."""
    B, S, _ = x.shape
    H, dn, dr = num_heads, qk_nope_dim, qk_rope_dim
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (B, S))
    q = linear(params["wq"], x).reshape(B, S, H, dn + dr)
    kv_a = linear(params["wkv_a"], x)
    c = rms_norm(params["kv_norm"], kv_a[..., :-dr], norm_eps)
    kv = linear(params["wkv_b"], c).reshape(B, S, H, dn + v_dim)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], positions, rope_theta)], -1)
    k_r = apply_rope(kv_a[..., None, -dr:], positions, rope_theta)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr))], -1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    attend = _chunked_attention if S > chunked_threshold \
        else _direct_attention
    out = attend(q, k, v, positions, positions, True, None, None, scale)
    return linear(params["wo"], out.reshape(B, S, H * v_dim))
