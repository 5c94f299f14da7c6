"""Model assembly: embeds + layer stacks (attention / MoE / SSM / hybrid)
with scan-over-layers, KV/state caches, prefill & decode entry points.

Layer stacking strategy
-----------------------
* homogeneous stacks (dense/moe/audio/vlm): one stacked params pytree with
  leading dim = num_layers, applied with ``jax.lax.scan`` so the compiled HLO
  contains ONE layer body regardless of depth, so compile time does not
  grow with it.
* patterned stacks (xlstm: 7×mlstm+1×slstm; zamba2: 8×mamba2+1×shared-attn):
  scan over ``num_super`` super-blocks; inside the scan body the pattern is
  unrolled (static, short).  zamba2's shared attention block reuses ONE weight
  set at every application (the paper's parameter-sharing trick) but carries a
  distinct KV cache per application.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.attention import (KVCache, PagedKVCache, attention,
                                    make_cache, make_paged_cache)
from repro.models.layers import (embed, init_embedding, init_linear, init_mlp,
                                 init_rmsnorm, linear, mlp, rms_norm, softcap,
                                 unembed)
from repro.models.moe import init_moe, moe_apply

BIG_WINDOW = 1 << 30  # "no window" sentinel usable as a dynamic operand


# ======================================================================
# init
# ======================================================================
def _init_attn_layer(key, cfg: ModelConfig):
    from repro.models.attention import init_attention_params
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": init_rmsnorm(cfg.d_model),
        "attn": init_attention_params(k1, cfg.d_model, cfg.num_heads,
                                      cfg.num_kv_heads, cfg.resolved_head_dim,
                                      cfg.qkv_bias),
        "ln2": init_rmsnorm(cfg.d_model),
    }
    if cfg.num_experts:
        p["moe"] = init_moe(k2, cfg.d_model, cfg.d_ff, cfg.num_experts)
    else:
        p["mlp"] = init_mlp(k3, cfg.d_model, cfg.d_ff, cfg.act)
    return p


def _init_block(key, kind: str, cfg: ModelConfig):
    if kind in ("attn", "attn_shared"):
        return _init_attn_layer(key, cfg)
    if kind == "mlstm":
        return ssm.init_mlstm(key, cfg.d_model, cfg.num_heads,
                              expansion=cfg.ssm_expansion,
                              conv_width=cfg.conv_width)
    if kind == "slstm":
        return ssm.init_slstm(key, cfg.d_model, cfg.num_heads)
    if kind == "mamba2":
        return ssm.init_mamba2(key, cfg.d_model, cfg.ssm_state_dim,
                               conv_width=cfg.conv_width)
    raise ValueError(kind)


def init_model(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {"final_norm": init_rmsnorm(cfg.d_model)}

    if cfg.frontend == "audio":
        params["frontend_proj"] = init_linear(keys[0], cfg.frontend_feat_dim,
                                              cfg.d_model)
        params["head"] = init_linear(keys[1], cfg.d_model, cfg.vocab_size)
    else:
        params["embed"] = init_embedding(keys[0], cfg.vocab_size, cfg.d_model)
        if not cfg.tie_embeddings:
            params["unembed"] = init_linear(keys[1], cfg.d_model,
                                            cfg.vocab_size)
    if cfg.frontend == "vision":
        params["patch_proj"] = init_linear(keys[2], cfg.frontend_feat_dim,
                                           cfg.d_model)

    if cfg.block_pattern:
        sup: Dict[str, Any] = {}
        pat = cfg.block_pattern
        for i, kind in enumerate(pat):
            if kind == "attn_shared":
                continue
            ks = jax.random.split(jax.random.fold_in(keys[3], i),
                                  cfg.num_super)
            sup[f"{kind}_{i}"] = jax.vmap(
                lambda k: _init_block(k, kind, cfg))(jnp.stack(ks))
        params["super"] = sup
        if "attn_shared" in pat:
            params["shared_attn"] = _init_block(keys[4], "attn", cfg)
    else:
        ks = jax.random.split(keys[3], cfg.num_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_block(k, "attn", cfg))(jnp.stack(ks))
    return params


def init_abstract(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of the params, in cfg.dtype — no allocation."""
    shapes = jax.eval_shape(lambda: init_model(jax.random.key(0), cfg))
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dt if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
        shapes)


# ======================================================================
# caches
# ======================================================================
def _stack_cache(make_one, n: int):
    one = make_one()
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                        one) if not isinstance(one, tuple) else jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               window_override: Optional[int] = None, dtype=None,
               per_layer: bool = False):
    """Stacked per-layer caches for decode.  Leading dim = layers/super.

    ``per_layer=True`` (local/global archs, unrolled decode only): returns a
    LIST of per-layer caches, each sized to ITS OWN window — gemma2's local
    layers then hold a 4096-slot ring instead of the full 32k context
    (half the KV memory on a 46-layer stack)."""
    dt = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim
    di_mlstm = cfg.d_model * cfg.ssm_expansion
    di = cfg.d_model * 2                      # mamba2 expansion fixed at 2

    def attn_cache(window):
        return make_cache(batch, max_seq, cfg.num_kv_heads, hd, window, dt)

    if cfg.block_pattern:
        caches: Dict[str, Any] = {}
        for i, kind in enumerate(cfg.block_pattern):
            if kind == "mlstm":
                one = ssm.mlstm_init_state(batch, cfg.num_heads,
                                           di_mlstm // cfg.num_heads,
                                           di_mlstm, cfg.conv_width, dt)
            elif kind == "slstm":
                one = ssm.slstm_init_state(batch, cfg.num_heads,
                                           cfg.d_model // cfg.num_heads)
            elif kind == "mamba2":
                one = ssm.mamba2_init_state(batch, di, cfg.ssm_state_dim,
                                            64, cfg.conv_width, dt)
            else:  # attn_shared: window per cfg
                w = window_override if window_override else cfg.sliding_window
                one = attn_cache(w)
            caches[f"{kind}_{i}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (cfg.num_super,) + x.shape).copy(), one)
        return caches

    # homogeneous attention stack; per-layer window possible (gemma2)
    windows = layer_windows(cfg, window_override)
    if per_layer:
        return [attn_cache(None if w == BIG_WINDOW else w) for w in windows]
    uniform = all(w == windows[0] for w in windows)
    if uniform:
        one = attn_cache(windows[0] if windows[0] != BIG_WINDOW else None)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.num_layers,) + x.shape).copy(), one)
    # mixed local/global: all caches sized max window (ring semantics only if
    # every layer is windowed).  Local layers still mask to their window.
    maxw = max(w for w in windows)
    one = attn_cache(None if maxw == BIG_WINDOW else maxw)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(
            x[None], (cfg.num_layers,) + x.shape).copy(), one)


def init_paged_cache(cfg: ModelConfig, batch: int, max_seq: int,
                     window_override: Optional[int] = None, dtype=None, *,
                     page_size: int, num_pages: int):
    """Paged variant of :func:`init_cache`: every attention node becomes a
    batch-free :class:`PagedKVCache` pool shared by all decode slots
    (page 0 = trash), addressed through an engine-owned page table.  Pages
    hold absolute positions (full depth — sliding windows apply purely via
    masking), so the per-node ring-vs-full distinction disappears.
    Recurrent (mLSTM/sLSTM/Mamba2) states are fixed-size per slot and stay
    batched exactly as in :func:`init_cache`."""
    dt = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim

    def paged_node(n):
        one = make_paged_cache(num_pages, page_size, cfg.num_kv_heads, hd, dt)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape).copy(), one)

    if cfg.block_pattern:
        caches = init_cache(cfg, batch, max_seq, window_override, dt)
        for i, kind in enumerate(cfg.block_pattern):
            if kind in ("attn", "attn_shared"):
                caches[f"{kind}_{i}"] = paged_node(cfg.num_super)
        return caches
    return paged_node(cfg.num_layers)


def layer_windows(cfg: ModelConfig, window_override: Optional[int] = None):
    """Static per-layer attention window list (BIG_WINDOW = unlimited)."""
    if cfg.block_pattern:
        n = sum(1 for k in cfg.layer_kinds if k == "attn_shared")
        w = window_override or cfg.sliding_window or BIG_WINDOW
        return [w] * n
    out = []
    for i in range(cfg.num_layers):
        if cfg.local_global:
            # even layers local (sliding window), odd layers global
            if i % 2 == 0:
                out.append(cfg.sliding_window or BIG_WINDOW)
            else:
                out.append(window_override or BIG_WINDOW)
        elif cfg.sliding_window:
            out.append(cfg.sliding_window)
        else:
            out.append(window_override or BIG_WINDOW)
    return out


# ======================================================================
# blocks
# ======================================================================
def _attn_block(lp, x, cfg: ModelConfig, positions, window, cache,
                page_table=None, paged_kernel: bool = False):
    h = rms_norm(lp["ln1"], x, cfg.norm_eps)
    a, new_cache = attention(
        lp["attn"], h, num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, positions=positions, causal=cfg.causal,
        window=window, attn_cap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
        cache=cache, page_table=page_table, paged_kernel=paged_kernel)
    x = x + a
    h = rms_norm(lp["ln2"], x, cfg.norm_eps)
    if "moe" in lp:
        m, aux = moe_apply(
            lp["moe"], h, num_experts=cfg.num_experts,
            top_k=cfg.experts_per_token, aux_coef=cfg.router_aux_coef,
            capacity_factor=cfg.moe_capacity_factor,
            route_block=cfg.moe_route_block)
    else:
        m, aux = mlp(lp["mlp"], h, cfg.act), jnp.float32(0.0)
    return x + m, new_cache, aux


def _freeze_idle(old, new, positions):
    """Pin recurrent state for decode rows at negative positions.

    A paged engine parks idle and still-prefilling slots at position -1;
    their attention writes fall into the trash page, and this is the
    recurrent-state counterpart: without it every batched decode step
    would advance (i.e. corrupt) the state a chunked prefill is building
    in that row.  Dense engines park idle rows at position 0, which keeps
    their legacy advance-and-overwrite behavior byte-identical."""
    keep = positions[:, 0] >= 0
    return jax.tree.map(
        lambda o, n: jnp.where(
            keep.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), old, new)


def _apply_kind(kind, lp, x, cfg, positions, window, cache,
                page_table=None, paged_kernel: bool = False):
    """Dispatch one block; returns (x, new_cache, aux)."""
    S = x.shape[1]
    if kind in ("attn", "attn_shared"):
        return _attn_block(lp, x, cfg, positions, window, cache,
                           page_table, paged_kernel)
    if kind == "mlstm":
        if S == 1 and cache is not None:
            y, st = ssm.mlstm_decode_step(lp, x, cache,
                                          num_heads=cfg.num_heads,
                                          expansion=cfg.ssm_expansion)
            st = _freeze_idle(cache, st, positions)
        else:
            y, st = ssm.mlstm_apply(lp, x, num_heads=cfg.num_heads,
                                    state=cache, chunk=min(256, S),
                                    expansion=cfg.ssm_expansion)
        return y, st, jnp.float32(0.0)
    if kind == "slstm":
        y, st = ssm.slstm_apply(lp, x, num_heads=cfg.num_heads, state=cache)
        if S == 1 and cache is not None:
            st = _freeze_idle(cache, st, positions)
        return y, st, jnp.float32(0.0)
    if kind == "mamba2":
        if S == 1 and cache is not None:
            y, st = ssm.mamba2_decode_step(lp, x, cache,
                                           state_dim=cfg.ssm_state_dim)
            st = _freeze_idle(cache, st, positions)
        else:
            y, st = ssm.mamba2_apply(lp, x, state_dim=cfg.ssm_state_dim,
                                     state=cache, chunk=min(256, S))
        return y, st, jnp.float32(0.0)
    raise ValueError(kind)


# ======================================================================
# stack
# ======================================================================
def apply_stack(params, cfg: ModelConfig, x, positions, caches=None,
                window_override: Optional[int] = None, remat: bool = False,
                unroll: bool = False, page_table=None,
                paged_kernel: bool = False):
    """Run the whole layer stack.  Returns (x, new_caches, aux_total).

    ``page_table`` (B, M) is closed over by the layer scan (like
    ``positions``) when the caches are paged — every paged node shares the
    ONE physical page-id space, so one table addresses them all."""
    if cfg.block_pattern:
        return _apply_patterned(params, cfg, x, positions, caches,
                                window_override, remat, page_table,
                                paged_kernel)
    if unroll and caches is not None:
        win_list = layer_windows(cfg, window_override)
        aux = jnp.float32(0.0)
        if isinstance(caches, list):
            # per-layer caches (heterogeneous sizes: local ring + global)
            new_list = []
            for i in range(cfg.num_layers):
                lp = jax.tree.map(lambda t: t[i], params["layers"])
                x, nc, a = _attn_block(lp, x, cfg, positions,
                                       win_list[i], caches[i],
                                       page_table, paged_kernel)
                aux = aux + a
                new_list.append(nc)
            return x, new_list, aux
        # unrolled decode: per-layer cache slices update in place (XLA can
        # alias the donated cache; the scan form double-buffers the whole
        # stacked cache as a loop carry — +13 GiB/dev on qwen decode_32k)
        new_caches = caches
        for i in range(cfg.num_layers):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            ci = jax.tree.map(lambda t: t[i], new_caches)
            x, nc, a = _attn_block(lp, x, cfg, positions,
                                   win_list[i], ci, page_table, paged_kernel)
            aux = aux + a
            # write the layer's updated cache back in place: chained DUS on
            # the (donated) stacked cache aliases instead of double-buffering
            new_caches = jax.tree.map(
                lambda full, piece: jax.lax.dynamic_update_index_in_dim(
                    full, piece, i, 0), new_caches, nc)
        return x, new_caches, aux
    windows = jnp.asarray(layer_windows(cfg, window_override), jnp.int32)

    def body(carry, xs):
        h, aux = carry
        lp, window, cache = xs
        h2, new_cache, a = _attn_block(lp, h, cfg, positions, window, cache,
                                       page_table, paged_kernel)
        return (h2, aux + a), new_cache

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), new_caches = jax.lax.scan(
        body_fn, (x, jnp.float32(0.0)),
        (params["layers"], windows, caches))
    return x, new_caches, aux


def _apply_patterned(params, cfg, x, positions, caches, window_override,
                     remat, page_table=None, paged_kernel: bool = False):
    pat = cfg.block_pattern
    w_attn = window_override or cfg.sliding_window or BIG_WINDOW

    def body(carry, xs):
        h, aux = carry
        sup_params, sup_caches = xs
        new_caches = {}
        for i, kind in enumerate(pat):
            key = f"{kind}_{i}"
            lp = params["shared_attn"] if kind == "attn_shared" \
                else sup_params[key]
            cache = sup_caches.get(key) if sup_caches else None
            h, nc, a = _apply_kind(kind, lp, h, cfg, positions, w_attn, cache,
                                   page_table, paged_kernel)
            aux = aux + a
            new_caches[key] = nc if nc is not None else jnp.float32(0)
        return (h, aux), new_caches

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux), new_caches = jax.lax.scan(
        body_fn, (x, jnp.float32(0.0)), (params["super"], caches))
    return x, new_caches, aux


# ======================================================================
# model entry points
# ======================================================================
def _embed_inputs(params, cfg: ModelConfig, batch):
    """batch dict -> (x (B,S,D), positions (B,S) or (S,), text_mask)."""
    if cfg.frontend == "audio":
        x = linear(params["frontend_proj"], batch["features"])
        S = x.shape[1]
        return x, jnp.arange(S, dtype=jnp.int32), None
    if cfg.frontend == "vision" and "patches" in batch:
        pe = linear(params["patch_proj"], batch["patches"])
        te = embed(params["embed"], batch["tokens"])
        x = jnp.concatenate([pe.astype(te.dtype), te], axis=1)
        S = x.shape[1]
        P = pe.shape[1]
        text_mask = jnp.concatenate(
            [jnp.zeros((P,), bool), jnp.ones((te.shape[1],), bool)])
        return x, jnp.arange(S, dtype=jnp.int32), text_mask
    x = embed(params["embed"], batch["tokens"])
    return x, jnp.arange(x.shape[1], dtype=jnp.int32), None


def _logits(params, cfg: ModelConfig, h):
    if cfg.frontend == "audio":
        lg = linear(params["head"], h)
    elif cfg.tie_embeddings:
        lg = unembed(params["embed"], h)
    else:
        lg = linear(params["unembed"], h)
    return softcap(lg, cfg.final_softcap)


def forward(params, cfg: ModelConfig, batch, *, remat: bool = False,
            window_override: Optional[int] = None):
    """Full forward pass -> (logits (B,S,V), aux)."""
    x, positions, _ = _embed_inputs(params, cfg, batch)
    x = x.astype(jnp.dtype(cfg.dtype))
    h, _, aux = apply_stack(params, cfg, x, positions, caches=None,
                            window_override=window_override, remat=remat)
    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    return _logits(params, cfg, h), aux


def _chunked_xent(h, cfg, params, labels, mask, chunk: int = 512):
    """Cross-entropy without materializing (B,S,V): scan over seq chunks."""
    B, S, D = h.shape
    L = min(chunk, S)
    nc = -(-S // L)
    pad = nc * L - S
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)))
    mp = jnp.pad(mask, ((0, 0), (0, pad)))
    hp = hp.reshape(B, nc, L, D).transpose(1, 0, 2, 3)
    lp = lp.reshape(B, nc, L).transpose(1, 0, 2)
    mp = mp.reshape(B, nc, L).transpose(1, 0, 2)

    def step(acc, xs):
        hc, lc, mc = xs
        logits = _logits(params, cfg, hc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - picked) * mc
        return (acc[0] + jnp.sum(nll), acc[1] + jnp.sum(mc)), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (hp, lp, mp))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch, *, remat: bool = True,
            window_override: Optional[int] = None):
    """Training loss (causal LM / masked prediction / text-only VLM)."""
    x, positions, text_mask = _embed_inputs(params, cfg, batch)
    x = x.astype(jnp.dtype(cfg.dtype))
    h, _, aux = apply_stack(params, cfg, x, positions, caches=None,
                            window_override=window_override, remat=remat)
    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    B, S, _ = h.shape

    if cfg.frontend == "audio":
        labels = batch["targets"]
        mask = batch["mask"].astype(jnp.float32)
        loss = _chunked_xent(h, cfg, params, labels, mask)
        return loss + aux

    if cfg.frontend == "vision" and "patches" in batch:
        T = batch["tokens"].shape[1]
        labels = jnp.pad(batch["labels"], ((0, 0), (S - T, 0)))
        mask = jnp.broadcast_to(text_mask[None], (B, S)).astype(jnp.float32)
        # next-token: positions predicting text tokens only
        h_shift = h[:, :-1]
        loss = _chunked_xent(h_shift, cfg, params, labels[:, 1:],
                             mask[:, 1:])
        return loss + aux

    labels = batch["labels"]
    mask = jnp.ones_like(labels, jnp.float32)
    loss = _chunked_xent(h[:, :-1], cfg, params, labels[:, 1:], mask[:, 1:])
    return loss + aux


def prefill(params, cfg: ModelConfig, batch, max_seq: int,
            window_override: Optional[int] = None,
            per_layer_cache: bool = False):
    """Prefill -> (last-position logits, filled caches)."""
    x, positions, _ = _embed_inputs(params, cfg, batch)
    x = x.astype(jnp.dtype(cfg.dtype))
    caches = init_cache(cfg, x.shape[0], max_seq, window_override,
                        jnp.dtype(cfg.dtype), per_layer=per_layer_cache)
    h, caches, _ = apply_stack(params, cfg, x, positions, caches=caches,
                               window_override=window_override,
                               unroll=per_layer_cache)
    h = rms_norm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    return _logits(params, cfg, h)[:, 0], caches


def decode_step(params, cfg: ModelConfig, token, pos, caches,
                window_override: Optional[int] = None,
                unroll: bool = False, page_table=None,
                paged_kernel: bool = False):
    """One decode step.  token: (B,) int32; pos: (B,) int32 absolute.
    ``page_table`` (B, M) is required when the caches are paged.

    Returns (logits (B,V), new_caches).
    """
    if cfg.frontend == "audio":
        raise ValueError("encoder-only model has no decode step")
    x = embed(params["embed"], token[:, None]).astype(jnp.dtype(cfg.dtype))
    positions = pos[:, None]
    h, caches, _ = apply_stack(params, cfg, x, positions, caches=caches,
                               window_override=window_override,
                               unroll=unroll, page_table=page_table,
                               paged_kernel=paged_kernel)
    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    return _logits(params, cfg, h)[:, 0], caches


def _is_cache_node(n):
    return isinstance(n, (KVCache, PagedKVCache))


def prefill_chunk(params, cfg: ModelConfig, tokens, positions, caches, slot,
                  page_table, window_override: Optional[int] = None,
                  paged_kernel: bool = False):
    """One B=1 prefill chunk for decode slot ``slot`` running directly
    against the engine's BATCHED cache tree: recurrent-state leaves are
    sliced out at the slot (batch axis 1) and written back, while paged
    attention nodes are batch-free and written in place through
    ``page_table`` (M,) — so chunked prefill never touches other slots'
    pages and interleaves with batched decode without copying caches.

    tokens/positions: (C,) int32 (absolute positions — chunk k >= 1 of a
    prompt passes positions starting at its chunk offset).  Returns
    (last-position logits (1, V), updated caches)."""
    x = embed(params["embed"], tokens[None]).astype(jnp.dtype(cfg.dtype))

    def view(n):
        if isinstance(n, PagedKVCache):
            return n
        if isinstance(n, KVCache):
            return jax.tree.map(
                lambda t: jax.lax.dynamic_slice_in_dim(t, slot, 1, 1), n)
        return jax.lax.dynamic_slice_in_dim(n, slot, 1, 1)

    view_caches = jax.tree.map(view, caches, is_leaf=_is_cache_node)
    h, new_view, _ = apply_stack(params, cfg, x, positions[None],
                                 caches=view_caches,
                                 window_override=window_override,
                                 page_table=page_table[None],
                                 paged_kernel=paged_kernel)

    def back(full, new):
        if isinstance(full, PagedKVCache):
            return new
        if isinstance(full, KVCache):
            return jax.tree.map(
                lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                    f, o, slot, 1), full, new)
        return jax.lax.dynamic_update_slice_in_dim(full, new, slot, 1)

    caches = jax.tree.map(back, caches, new_view, is_leaf=_is_cache_node)
    h = rms_norm(params["final_norm"], h[:, -1:], cfg.norm_eps)
    return _logits(params, cfg, h)[:, 0], caches


# ======================================================================
# latent-attention MoE stack (DeepSeek-V3 layout), for policy training
# ======================================================================
# ``first_dense_layers`` dense layers, then MoE layers, each scanned over
# a stacked params pytree; every layer is pre-norm MLA plus an MLP (dense)
# or held routed experts and shared experts (``models/moe.py``).  The
# embedding and the head cover the vocabulary slice held here.


def latent_moe_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    """The params pytree of :func:`init_latent_moe` as shapes."""
    D, H = cfg.d_model, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r, V, F = cfg.kv_lora_rank, cfg.vocab_size, cfg.d_ff
    E, Eh = cfg.num_experts, cfg.experts_held or cfg.num_experts
    Fs = cfg.num_shared_experts * F

    def layer(n, ffn):
        one = {"ln1": {"scale": (D,)}, "ln2": {"scale": (D,)},
               "mla": {"wq": {"w": (D, H * (dn + dr))},
                       "wkv_a": {"w": (D, r + dr)},
                       "kv_norm": {"scale": (r,)},
                       "wkv_b": {"w": (r, H * (dn + dv))},
                       "wo": {"w": (H * dv, D)}}, **ffn}
        return jax.tree.map(lambda s: (n,) + s, one,
                            is_leaf=lambda s: isinstance(s, tuple))

    Fd = cfg.dense_d_ff
    return {
        "embed": {"table": (V, D)},
        "dense": layer(cfg.first_dense_layers,
                       {"mlp": {"wi": (D, Fd), "wg": (D, Fd),
                                "wo": (Fd, D)}}),
        "layers": layer(cfg.num_layers - cfg.first_dense_layers,
                        {"moe": {"router": (D, E), "bias": (E,),
                                 "experts": {"wi": (Eh, D, F),
                                             "wg": (Eh, D, F),
                                             "wo": (Eh, F, D)},
                                 "shared": {"wi": (D, Fs), "wg": (D, Fs),
                                            "wo": (Fs, D)}}}),
        "final_norm": {"scale": (D,)},
        "unembed": {"w": (D, V)},
    }


def init_latent_moe(key, cfg: ModelConfig) -> Dict[str, Any]:
    """Weights drawn leaf by leaf in the pytree's flattening order: leaf i
    is ``normal(fold_in(key, i), shape) * scale``, the scale being the
    fan-in (second-to-last dim) to the power -1/2 for a matrix, 1 for the
    embedding, ``cfg.router_bias_std`` for the choice bias; norm scales
    are ones."""
    paths, tree = jax.tree_util.tree_flatten_with_path(
        latent_moe_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))

    def draw(i, path, shape):
        name = path[-1].key
        if name == "scale":
            return jnp.ones(shape, jnp.float32)
        scale = {"table": 1.0, "bias": cfg.router_bias_std}.get(
            name, shape[-2] ** -0.5 if len(shape) >= 2 else 1.0)
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32) * scale

    return jax.tree_util.tree_unflatten(
        tree, [draw(i, p, s) for i, (p, s) in enumerate(paths)])


def _latent_layer(lp, x, cfg: ModelConfig, positions, valid, first_expert):
    from repro.models.attention import mla_with_blocks
    from repro.models.moe import routed_moe_apply
    h = rms_norm(lp["ln1"], x, cfg.norm_eps)
    with jax.named_scope("mla"):
        a, counters = mla_with_blocks(
            lp["mla"], h, positions, num_heads=cfg.num_heads,
            qk_nope_dim=cfg.qk_nope_head_dim,
            qk_rope_dim=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
            lengths=valid.sum(-1))
        x = x + a
    h = rms_norm(lp["ln2"], x, cfg.norm_eps)
    if "moe" in lp:
        m, moe = routed_moe_apply(
            lp["moe"], h, valid, top_k=cfg.experts_per_token,
            routed_scale=cfg.routed_scale, first_expert=first_expert)
        return x + m, dict(moe, **counters)
    return x + mlp(lp["mlp"], h, cfg.act), counters


def latent_moe_hidden(params, cfg: ModelConfig, tokens, valid,
                      first_expert: int = 0):
    """tokens, valid: (B, S) -> (final-normed hidden (B, S, D), counters
    of the MoE layers, each stacked (layers, ...), and ``attn_blocks`` /
    ``attn_blocks_full``: the attention block pairs one pass computes and
    those of the full squares, summed over every layer).  Rows are
    right-padded: ``valid`` is a prefix of each row.  Each layer is
    rematerialized under a gradient."""
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def body(h, lp):
        return _latent_layer(lp, h, cfg, positions, valid, first_expert)

    body = jax.checkpoint(body)
    x, dense = jax.lax.scan(body, x, params["dense"])
    x, counters = jax.lax.scan(body, x, params["layers"])
    for k, n in dense.items():          # the attention blocks
        counters[k] = jnp.sum(n) + jnp.sum(counters[k])
    return rms_norm(params["final_norm"], x, cfg.norm_eps), counters


def token_logprobs(params, cfg: ModelConfig, tokens, valid,
                   first_expert: int = 0, chunk: int = 512):
    """Log-probability of each next token over the vocabulary slice:
    (B, S - 1) for ``tokens[:, 1:]``, plus the counters of
    :func:`latent_moe_hidden`.  The head is applied over position chunks
    (as ``_chunked_xent``), each rematerialized, so (B, S, V) logits never
    exist at once."""
    h, counters = latent_moe_hidden(params, cfg, tokens, valid,
                                    first_expert)
    h, labels = h[:, :-1], tokens[:, 1:]
    B, S, D = h.shape
    L = min(chunk, S)
    nc = -(-S // L)
    pad = nc * L - S
    hp = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    hp = hp.reshape(B, nc, L, D).transpose(1, 0, 2, 3)
    lp = jnp.pad(labels, ((0, 0), (0, pad))).reshape(B, nc, L)
    lp = lp.transpose(1, 0, 2)

    @jax.checkpoint
    def step(_, xs):
        hc, lc = xs
        with jax.named_scope("lm_head"):
            logits = linear(params["unembed"], hc).astype(jnp.float32)
            picked = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
            return None, picked - jax.nn.logsumexp(logits, axis=-1)

    _, out = jax.lax.scan(step, None, (hp, lp))
    return out.transpose(1, 0, 2).reshape(B, nc * L)[:, :S], counters
