"""Paged gather-decode attention Pallas TPU kernel.

One decode step (Sq == 1 per batch row) reading a slot's KV cache THROUGH
its page table: the physical cache is a shared pool of fixed-size pages
``(num_pages, page, n_kv, hd)`` and each batch row owns a row of page ids
``table (B, M)`` mapping virtual page v (absolute positions
``[v*page, (v+1)*page)``) to a physical page (-1 = unmapped).  The table
and the per-row absolute positions ride in as scalar-prefetch operands, so
the k/v BlockSpec index maps dereference the table directly — the kernel
never materializes the gathered (B, M*page, ...) view the jnp fallback in
``repro.models.attention`` builds.

Grid: (batch * kv_heads, M) — the page axis is innermost/sequential, so
the online-softmax accumulators live in VMEM scratch across it exactly as
in ``flash_attention.py``.  Invalid pages (table < 0) index the trash page
0 and are fully masked via the prefetched table; empty page slots are
masked by ``slot_pos < 0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_BIG_WINDOW = 1 << 30


def _kernel(tbl_ref, pos_ref, win_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, softcap, kv_heads, num_pages):
    h = pl.program_id(0)
    mi = pl.program_id(1)
    b = h // kv_heads

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (page, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, page)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qpos = pos_ref[b]
    kpos = sp_ref[0]                                  # (1, page)
    valid = (kpos >= 0) & (kpos <= qpos) & (kpos > qpos - win_ref[0])
    valid &= tbl_ref[b, mi] >= 0
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # masked entries contribute exactly 0, also when a whole page is
    # masked (m_new == NEG_INF): unwritten pool slots never leak into acc
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    v = v_ref[0, 0].astype(jnp.float32)               # (page, hd)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(mi == num_pages - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, slot_pos, table, positions,
                           *, window=None, softcap=None, scale=None,
                           interpret: bool = False):
    """q: (B, H, hd) one decode token per row; k/v pages: (N, page, KH, hd);
    slot_pos: (N, page) absolute position per page slot (-1 empty); table:
    (B, M) physical page per virtual page (-1 unmapped); positions: (B,)
    absolute q position per row.  Returns (B, H, hd)."""
    B, H, hd = q.shape
    N, page, KH, _ = k_pages.shape
    M = table.shape[1]
    assert H % KH == 0, "GQA requires q heads to be a multiple of kv heads"
    G = H // KH
    scale = scale if scale is not None else hd ** -0.5
    # window may be a traced scalar (per-layer windows under scan)
    win = jnp.full((1,), _BIG_WINDOW, jnp.int32) if window is None \
        else jnp.asarray(window, jnp.int32).reshape(1)

    qh = q.reshape(B * KH, G, hd)                     # head h = kh*G + g
    kp = k_pages.transpose(0, 2, 1, 3)                # (N, KH, page, hd)
    vp = v_pages.transpose(0, 2, 1, 3)
    # one page's positions as a (1, page) row: a (1, page) block of the
    # (N, page) array would break the TPU rule that the last two block
    # dims divide by (8, 128) or span the array
    sp = slot_pos.reshape(N, 1, page)

    def page_row(h, m, tbl, pos, w):
        return jnp.maximum(tbl[h // KH, m], 0), h % KH, 0, 0

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap,
                               kv_heads=KH, num_pages=M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B * KH, M),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda h, m, tbl, pos, w: (h, 0, 0)),
            pl.BlockSpec((1, 1, page, hd), page_row),
            pl.BlockSpec((1, 1, page, hd), page_row),
            pl.BlockSpec((1, 1, page),
                         lambda h, m, tbl, pos, w:
                         (jnp.maximum(tbl[h // KH, m], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda h, m, tbl, pos, w:
                               (h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KH, G, hd), q.dtype),
        interpret=interpret,
    )(table, positions, win, qh, kp, vp, sp)
    return out.reshape(B, H, hd)
