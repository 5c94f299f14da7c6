"""Flash attention Pallas TPU kernel: blocked online softmax with GQA,
causal / sliding-window masking, logit softcap, per-row real lengths,
block skipping, and a FlashAttention-2 backward.

TPU adaptation: VMEM-tiled q/k/v blocks; the innermost grid axis is
sequential on TPU, so the running max / denominator / accumulator of a
query block (forward, ``dq``) or the key block's gradients (``dk``/``dv``)
live in VMEM scratch across it, and a block's scores never leave VMEM.

Block skipping: a (query block, key block) pair is computed only if it
holds a live pair, i.e. a query and a key both inside the row's real
length (``lengths``, a scalar-prefetch operand; rows are right-padded),
the key not after the query (causal) and inside the window.  A skipped
pair costs no compute and no DMA: the index maps clamp to a block the
pipeline already holds.  Only pairs not wholly live apply the element
mask.  Query rows at or past the length get zero output and zero
gradient.

Products: the operands of every product are cast to ``dot_dtype`` (the
caller's choice; q's dtype by default) and accumulate in f32.  Softmax
statistics, accumulators, ``o`` and every gradient are float32.

Grid (forward and ``dq``): (batch * q_heads, num_q_blocks, num_kv_blocks);
``dk``/``dv``: (batch * q_heads, num_kv_blocks, num_q_blocks), each query
head's key gradients summed over its group outside the kernel.
  q block:   (block_q, hd)     k block: (block_k, hd)    v block: (block_k, dv)
  scratch:   acc (block_q, dv) f32, m/l (block_q, 128) f32 (lane-replicated)
The value width ``dv`` may differ from ``hd`` (latent attention: 192/128).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_NT = (((1,), (1,)), ((), ()))          # a @ b.T


class _Cfg(NamedTuple):
    causal: bool
    window: Optional[int]
    softcap: Optional[float]
    scale: float
    block_q: int
    block_k: int
    heads: int            # query heads per batch row
    group: int            # query heads per kv head
    seq_q: int
    seq_kv: int
    dtypes: tuple         # of q, k, v (their gradients')
    dot_dtype: str        # operands of the products
    interpret: bool


def block_state(q0, k0, qlen, klen, *, block_q, block_k, causal, window):
    """(live, full) of the block pair whose query block starts at ``q0``
    and key block at ``k0``: ``live`` if some (query, key) pair in it is
    live, ``full`` if every pair is.  A pair is live if the query is below
    ``qlen``, the key below ``klen``, the key not after the query
    (``causal``) and after ``query - window``.  Works on scalars and on
    broadcast arrays."""
    q1, k1 = q0 + block_q - 1, k0 + block_k - 1
    qh = jnp.minimum(q1, qlen - 1)          # last live query of the block
    kh = jnp.minimum(k1, klen - 1)
    live = (q0 <= qh) & (k0 <= kh)
    full = (q1 < qlen) & (k1 < klen)
    if causal:
        live &= k0 <= qh
        full &= k1 <= q0
    if window is not None:
        live &= kh > q0 - window
        full &= k0 > q1 - window
    return live, full


def block_counts(lengths, seq_q, seq_kv, *, block_q, block_k,
                 causal=True, window=None):
    """(block pairs computed, block pairs in the full grid), summed over
    the rows of ``lengths`` (B,): what one call computes per head."""
    nq, nk = pl.cdiv(seq_q, block_q), pl.cdiv(seq_kv, block_k)
    L = lengths.astype(jnp.int32)[:, None, None]
    q0 = jnp.arange(nq, dtype=jnp.int32)[None, :, None] * block_q
    k0 = jnp.arange(nk, dtype=jnp.int32)[None, None, :] * block_k
    live, _ = block_state(q0, k0, jnp.minimum(L, seq_q),
                          jnp.minimum(L, seq_kv), block_q=block_q,
                          block_k=block_k, causal=causal, window=window)
    return jnp.sum(live, dtype=jnp.int32), \
        jnp.int32(lengths.shape[0] * nq * nk)


# ------------------------------------------------------------ index maps --
def _lens(len_ref, h, c: _Cfg):
    L = len_ref[h // c.heads]
    return jnp.minimum(L, c.seq_q), jnp.minimum(L, c.seq_kv)


def _last(n, b):
    """Index of the block holding position ``n - 1`` (0 if ``n`` <= 0)."""
    return jnp.maximum(n - 1, 0) // b


def _kv_range(i, qlen, klen, c: _Cfg):
    """First and last key block a live pair of query block ``i`` reads
    (the last query block holding a live query stands in for the ones
    past it, so a dead query block reads what was read before it)."""
    i = jnp.minimum(i, _last(qlen, c.block_q))
    q0 = i * c.block_q
    qh = jnp.minimum(q0 + c.block_q, qlen) - 1
    kh = jnp.minimum(klen - 1, qh) if c.causal else klen - 1
    hi = jnp.maximum(kh, 0) // c.block_k
    lo = 0
    if c.window is not None:
        lo = jnp.minimum(jnp.maximum(q0 - c.window + 1, 0) // c.block_k, hi)
    return lo, hi


def _q_range(j, qlen, klen, c: _Cfg):
    """First and last query block a live pair of key block ``j`` reads
    (the backward takes no window)."""
    j = jnp.minimum(j, _last(klen, c.block_k))
    hi = _last(qlen, c.block_q)
    lo = jnp.minimum(j * c.block_k // c.block_q, hi) if c.causal else 0
    return lo, hi


def _kv_row(h, c: _Cfg):
    # grid row h = b * H + q_head  ->  kv row = b * KH + q_head // G
    KH = c.heads // c.group
    return (h // c.heads) * KH + (h % c.heads) // c.group


def _q_major_maps(c: _Cfg):
    """Index maps of a grid (rows, q blocks, kv blocks)."""
    def q_map(h, i, j, len_ref):
        qlen, _ = _lens(len_ref, h, c)
        return h, jnp.minimum(i, _last(qlen, c.block_q)), 0

    def kv_map(h, i, j, len_ref):
        qlen, klen = _lens(len_ref, h, c)
        lo, hi = _kv_range(i, qlen, klen, c)
        dead = i * c.block_q >= qlen          # holds what it last read
        return _kv_row(h, c), jnp.where(dead, hi, jnp.clip(j, lo, hi)), 0

    def row_map(h, i, j, len_ref):           # (rows, 1, S) statistics
        qlen, _ = _lens(len_ref, h, c)
        return h, 0, jnp.minimum(i, _last(qlen, c.block_q))

    def out_map(h, i, j, len_ref):
        return h, i, 0
    return q_map, kv_map, row_map, out_map


def _kv_major_maps(c: _Cfg):
    """Index maps of a grid (rows, kv blocks, q blocks)."""
    def q_index(h, j, i, len_ref):
        qlen, klen = _lens(len_ref, h, c)
        lo, hi = _q_range(j, qlen, klen, c)
        dead = j * c.block_k >= klen          # holds what it last read
        return jnp.where(dead, hi, jnp.clip(i, lo, hi))

    def q_map(h, j, i, len_ref):
        return h, q_index(h, j, i, len_ref), 0

    def row_map(h, j, i, len_ref):
        return h, 0, q_index(h, j, i, len_ref)

    def kv_map(h, j, i, len_ref):
        _, klen = _lens(len_ref, h, c)
        return _kv_row(h, c), jnp.minimum(j, _last(klen, c.block_k)), 0

    def out_map(h, j, i, len_ref):
        return h, j, 0
    return q_map, kv_map, row_map, out_map


# --------------------------------------------------------------- kernels --
def _scores(a, b, c: _Cfg):
    """a @ b.T in f32, softcapped."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    if c.softcap is not None:
        s = jnp.tanh(s / c.softcap) * c.softcap
    return s


def _mask(s, q0, k0, qlen, klen, c: _Cfg, *, kv_major: bool):
    """Kill the dead pairs of a score block: (bq, bk), or (bk, bq) when
    ``kv_major``."""
    qa, ka = (1, 0) if kv_major else (0, 1)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, qa)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, ka)
    ok = (qpos < qlen) & (kpos < klen)
    if c.causal:
        ok &= kpos <= qpos
    if c.window is not None:
        ok &= kpos > qpos - c.window
    return jnp.where(ok, s, NEG_INF)


def _run_blocks(q0, k0, qlen, klen, c: _Cfg, body):
    """Run ``body(masked)`` on a live block pair: unmasked where every
    pair is live."""
    live, full = block_state(q0, k0, qlen, klen, block_q=c.block_q,
                             block_k=c.block_k, causal=c.causal,
                             window=c.window)
    pl.when(live & full)(functools.partial(body, False))
    pl.when(live & ~full)(functools.partial(body, True))


def _fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *rest, c: _Cfg, nk):
    lse_ref = rest[0] if len(rest) == 4 else None
    acc_ref, m_ref, l_ref = rest[-3:]
    h, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qlen, klen = _lens(len_ref, h, c)
    q0, k0 = i * c.block_q, j * c.block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked):
        s = _scores(q_ref[...], k_ref[...], c)                 # (bq, bk)
        if masked:
            s = _mask(s, q0, k0, qlen, klen, c, kv_major=False)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[...]
        acc_ref[...] = acc_ref[...] * corr[:, :1] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    _run_blocks(q0, k0, qlen, klen, c, body)

    @pl.when(j == nk - 1)
    def _finalize():
        real = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (c.block_q, 1), 0) < qlen
        l = l_ref[...][:, :1]
        o_ref[...] = jnp.where(real, acc_ref[...] / jnp.maximum(l, 1e-30),
                               0.0).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = jnp.where(real & (l_ref[...] > 0),
                                   m_ref[...] + jnp.log(l_ref[...]), 0.0)


def _dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
               dq_ref, acc_ref, *, c: _Cfg, nk):
    h, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qlen, klen = _lens(len_ref, h, c)
    q0, k0 = i * c.block_q, j * c.block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        k = k_ref[...]
        s = _scores(q_ref[...], k, c)                          # (bq, bk)
        if masked:
            s = _mask(s, q0, k0, qlen, klen, c, kv_major=False)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        acc_ref[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    _run_blocks(q0, k0, qlen, klen, c, body)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[...] = acc_ref[...] * c.scale


def _dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, c: _Cfg, nq):
    h, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qlen, klen = _lens(len_ref, h, c)
    q0, k0 = i * c.block_q, j * c.block_k

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        q, do = q_ref[...], do_ref[...]
        s = _scores(k_ref[...], q, c)                          # (bk, bq)
        if masked:
            s = _mask(s, q0, k0, qlen, klen, c, kv_major=True)
        p = jnp.exp(s - lse_ref[...])
        dv_acc[...] += jax.lax.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        dk_acc[...] += jax.lax.dot(ds.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    _run_blocks(q0, k0, qlen, klen, c, body)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...]
        dv_ref[...] = dv_acc[...]


# ------------------------------------------------------------ pallas calls --
def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _forward(lengths, qh, kh, vh, c: _Cfg, residuals: bool):
    """qh: (rows, Sq', hd) scaled; kh/vh: (kv rows, Skv', hd/dv); the
    sequence axes padded to whole blocks.  Returns o (rows, Sq', dv) f32
    and, with ``residuals``, the log-sum-exp (rows, 1, Sq') f32."""
    R, Sq, hd = qh.shape
    dv = vh.shape[-1]
    nq, nk = Sq // c.block_q, kh.shape[1] // c.block_k
    q_map, kv_map, _, out_map = _q_major_maps(c)
    bq, bk = c.block_q, c.block_k
    out_specs = [pl.BlockSpec((None, bq, dv), out_map)]
    out_shape = [jax.ShapeDtypeStruct((R, Sq, dv), jnp.float32)]
    if residuals:
        out_specs.append(pl.BlockSpec((None, bq, LANES), out_map))
        out_shape.append(jax.ShapeDtypeStruct((R, Sq, LANES), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, nq, nk),
            in_specs=[pl.BlockSpec((None, bq, hd), q_map),
                      pl.BlockSpec((None, bk, hd), kv_map),
                      pl.BlockSpec((None, bk, dv), kv_map)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),
                            pltpu.VMEM((bq, LANES), jnp.float32),
                            pltpu.VMEM((bq, LANES), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=c.interpret, name="attention",
    )(lengths, qh, kh, vh)
    if not residuals:
        return out[0], None
    return out[0], out[1][..., 0][:, None, :]


def _backward(lengths, qh, kh, vh, do, lse, di, c: _Cfg):
    """dq (rows, Sq', hd) and per-query-head dk/dv (rows, Skv', hd/dv),
    all f32."""
    R, Sq, hd = qh.shape
    Skv, dv = kh.shape[1], vh.shape[-1]
    nq, nk = Sq // c.block_q, Skv // c.block_k
    bq, bk = c.block_q, c.block_k
    operands = (lengths, qh, kh, vh, do, lse, di)

    q_map, kv_map, row_map, out_map = _q_major_maps(c)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, c=c, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, nq, nk),
            in_specs=[pl.BlockSpec((None, bq, hd), q_map),
                      pl.BlockSpec((None, bk, hd), kv_map),
                      pl.BlockSpec((None, bk, dv), kv_map),
                      pl.BlockSpec((None, bq, dv), q_map),
                      pl.BlockSpec((None, 1, bq), row_map),
                      pl.BlockSpec((None, 1, bq), row_map)],
            out_specs=pl.BlockSpec((None, bq, hd), out_map),
            scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, Sq, hd), jnp.float32),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=c.interpret, name="attention_dq",
    )(*operands)

    q_map, kv_map, row_map, out_map = _kv_major_maps(c)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, c=c, nq=nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, nk, nq),
            in_specs=[pl.BlockSpec((None, bq, hd), q_map),
                      pl.BlockSpec((None, bk, hd), kv_map),
                      pl.BlockSpec((None, bk, dv), kv_map),
                      pl.BlockSpec((None, bq, dv), q_map),
                      pl.BlockSpec((None, 1, bq), row_map),
                      pl.BlockSpec((None, 1, bq), row_map)],
            out_specs=[pl.BlockSpec((None, bk, hd), out_map),
                       pl.BlockSpec((None, bk, dv), out_map)],
            scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                            pltpu.VMEM((bk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((R, Skv, hd), jnp.float32),
                   jax.ShapeDtypeStruct((R, Skv, dv), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=c.interpret, name="attention_dkv",
    )(*operands)
    return dq, dk, dv_


# ---------------------------------------------------------- layout, VJP --
def _heads_major(x, seq, dtype):
    """(B, S, N, d) -> (B * N, seq, d) in ``dtype``, zero-padded."""
    B, S, N, d = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(B * N, S, d).astype(dtype)
    return x if seq == S else jnp.pad(x, ((0, 0), (0, seq - S), (0, 0)))


def _seq_major(x, B, S, dtype):
    """(B * N, S', d) -> (B, S, N, d) in ``dtype``."""
    _, Sp, d = x.shape
    x = x if Sp == S else x[:, :S]
    return x.reshape(B, -1, S, d).transpose(0, 2, 1, 3).astype(dtype)


def _operands(q, k, v, c: _Cfg):
    mxu = jnp.dtype(c.dot_dtype)
    Sq = pl.cdiv(c.seq_q, c.block_q) * c.block_q
    Skv = pl.cdiv(c.seq_kv, c.block_k) * c.block_k
    return (_heads_major(q.astype(jnp.float32) * c.scale, Sq, mxu),
            _heads_major(k, Skv, mxu), _heads_major(v, Skv, mxu))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attention(q, k, v, lengths, c: _Cfg):
    o, _ = _forward(lengths, *_operands(q, k, v, c), c, residuals=False)
    return _seq_major(o, q.shape[0], c.seq_q, q.dtype)


def _attention_fwd(q, k, v, lengths, c: _Cfg):
    if c.softcap is not None or c.window is not None:
        raise NotImplementedError("the attention kernel's backward takes "
                                  "no softcap and no window")
    ops_ = _operands(q, k, v, c)
    o, lse = _forward(lengths, *ops_, c, residuals=True)
    return _seq_major(o, q.shape[0], c.seq_q, q.dtype), \
        (ops_, o, lse, lengths)


def _attention_bwd(c: _Cfg, res, g):
    (qh, kh, vh), o, lse, lengths = res
    qdt, kdt, vdt = c.dtypes
    B, KH = g.shape[0], c.heads // c.group
    do = _heads_major(g, o.shape[1], jnp.float32)
    di = jnp.sum(do * o, axis=-1)[:, None, :]
    dq, dk, dv = _backward(lengths, qh, kh, vh, do.astype(qh.dtype), lse,
                           di, c)

    def kv_grad(x, dt):        # sum each kv head's group of query heads
        R, S, d = x.shape
        x = x.reshape(B * KH, c.group, S, d).sum(1)
        return _seq_major(x, B, c.seq_kv, dt)
    return (_seq_major(dq, B, c.seq_q, qdt), kv_grad(dk, kdt),
            kv_grad(dv, vdt), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q, k, v, lengths=None, *, causal: bool = True,
                    window=None, softcap=None, scale=None, dot_dtype=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, hd); k: (B, Skv, KH, hd); v: (B, Skv, KH, dv);
    lengths: (B,) int real positions per row (rows right-padded; ``None``:
    every position is real).  ``dot_dtype``: the products' operand dtype
    (``None``: q's).  Returns (B, Sq, H, dv) in q's dtype; rows at or past
    a row's length are zero.  Differentiable in q, k and v (the backward
    refuses a softcap and a window)."""
    c = _config(q, k, v, causal=causal, window=window, softcap=softcap,
                scale=scale, dot_dtype=dot_dtype, block_q=block_q,
                block_k=block_k, interpret=interpret)
    if lengths is None:
        lengths = jnp.full((q.shape[0],), max(c.seq_q, c.seq_kv), jnp.int32)
    return _attention(q, k, v, lengths.astype(jnp.int32), c)


def _config(q, k, v, *, causal, window, softcap, scale, dot_dtype,
            block_q, block_k, interpret) -> _Cfg:
    _, Sq, H, hd = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    assert H % KH == 0, "GQA requires q heads to be a multiple of kv heads"
    return _Cfg(causal=causal, window=window, softcap=softcap,
                scale=scale if scale is not None else hd ** -0.5,
                block_q=min(block_q, Sq), block_k=min(block_k, Skv),
                heads=H, group=H // KH, seq_q=Sq, seq_kv=Skv,
                dtypes=(q.dtype.name, k.dtype.name, v.dtype.name),
                dot_dtype=jnp.dtype(dot_dtype or q.dtype).name,
                interpret=interpret)
