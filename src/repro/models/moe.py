"""Mixture-of-Experts layers.

``moe_apply``: softmax top-k router + capacity-bucketed dispatch, every
expert held (mixtral, granite, serving).  ``routed_moe_apply`` (end of the
file): DeepSeek-V3 routing over the published experts, of which this chip
holds a share, dropless through the grouped expert kernel.

Dispatch is *grouped*: tokens are routed within their (sharded) batch row.
The scatter/gather is expressed BATCHED (leading B dim everywhere, no vmap)
with explicit sharding constraints on every buffer — GSPMD cannot propagate
the batch sharding through a scatter with computed indices, and without the
constraints the expert intermediates materialize group-REPLICATED
(measured: 8.75 GiB f32[8,256,1280,896] tensors on mixtral train_4k,
~80 GiB/device total; see EXPERIMENTS.md §Perf Pair A).

Expert FFN weights carry the expert dim and are tensor-parallel over the
``model`` axis inside each expert (E rarely divides the 16-wide model
axis); FSDP placement options are in ``repro.dist.partition.param_specs``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import mlp
from repro.utils import lecun_init

# ---------------------------------------------------------------------------
# sharding hook (set by the launcher, like transformer.set_activation_sharding)
_GROUP_AXIS = None
_MODEL_AXIS = None


def set_moe_sharding(group_axis, model_axis="model"):
    """group_axis: mesh axis (or tuple) the batch/group dim shards over;
    model_axis: TP axis the expert hidden dim (F) shards over."""
    global _GROUP_AXIS, _MODEL_AXIS
    _GROUP_AXIS = group_axis
    _MODEL_AXIS = model_axis if group_axis is not None else None


def _constrain(x, *tail):
    """tail entries: None or "model" (resolved to the configured TP axis).
    NOTE a PartitionSpec constraint is TOTAL — None dims force replication,
    so the F dim must be named here or GSPMD computes the full unsharded
    expert hidden per device (measured 3.1x dot-FLOPs on mixtral)."""
    if _GROUP_AXIS is None:
        return x
    spec = [_GROUP_AXIS] + [(_MODEL_AXIS if t == "model" else t)
                            for t in tail[:x.ndim - 1]]
    return jax.lax.with_sharding_constraint(x, P(*spec))


def init_moe(key, d_model: int, d_ff: int, num_experts: int):
    kr, ki, kg, ko = jax.random.split(key, 4)
    return {
        "router": lecun_init(kr, (d_model, num_experts)),
        "wi": lecun_init(ki, (num_experts, d_model, d_ff), fan_in=d_model),
        "wg": lecun_init(kg, (num_experts, d_model, d_ff), fan_in=d_model),
        "wo": lecun_init(ko, (num_experts, d_ff, d_model), fan_in=d_ff),
    }


def _route_group(x, logits, top_k: int, capacity: int, num_experts: int):
    """Per-group routing.  x: (S, D); logits: (S, E).

    Returns (slot (S,k), gate (S,k), valid (S,k)) where slot indexes a flat
    (E*capacity) dispatch buffer.
    """
    S = x.shape[0]
    gate_all = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, expert = jax.lax.top_k(gate_all, top_k)            # (S,k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    # flatten in token-major order => earlier tokens win capacity slots
    flat_e = expert.reshape(-1)                               # (S*k,)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                      # (S*k, E)
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    valid = pos < capacity
    slot = jnp.where(valid, flat_e * capacity + pos, num_experts * capacity)
    return slot.reshape(S, top_k), gate.astype(x.dtype), valid.reshape(S, top_k)


def _dispatch(params, x, logits, num_experts: int, top_k: int,
              capacity_factor: float):
    """Capacity-bucketed dispatch over groups = leading dim.  x: (B, S, D),
    logits: (B, S, E) -> out (B, S, D)."""
    B, S, D = x.shape
    E, k = num_experts, top_k
    dt = x.dtype
    capacity = max(int(S * k / E * capacity_factor), k)

    # per-group index math (cheap int ops; vmap only over routing)
    slot, gate, valid = jax.vmap(
        lambda xg, lg: _route_group(xg, lg, k, capacity, E))(x, logits)
    flat_slot = slot.reshape(B, S * k)                        # (B,S*k)

    # batched scatter into the (E*capacity) dispatch buffer per group
    xk = jnp.repeat(x, k, axis=1)                             # (B,S*k,D)
    bidx = jnp.arange(B)[:, None]
    buf = jnp.zeros((B, E * capacity + 1, D), dt)
    buf = buf.at[bidx, flat_slot].add(xk)
    buf = _constrain(buf, None, None)
    bufe = buf[:, :-1].reshape(B, E, capacity, D)
    bufe = _constrain(bufe, None, None, None)

    h = jnp.einsum("becd,edf->becf", bufe, params["wi"].astype(dt))
    g = jnp.einsum("becd,edf->becf", bufe, params["wg"].astype(dt))
    h = _constrain(jax.nn.silu(g) * h, None, None, "model")
    out_buf = jnp.einsum("becf,efd->becd", h, params["wo"].astype(dt))
    out_buf = _constrain(out_buf, None, None, None)

    out_flat = jnp.concatenate(
        [out_buf.reshape(B, E * capacity, D),
         jnp.zeros((B, 1, D), dt)], axis=1)
    y = jnp.take_along_axis(out_flat, flat_slot[..., None], axis=1)
    y = y.reshape(B, S, k, D)
    w = (gate * valid.astype(gate.dtype))[..., None]
    out = jnp.sum(y * w.astype(y.dtype), axis=2)
    return _constrain(out, None, None)


def moe_apply(params, x, *, num_experts: int, top_k: int,
              capacity_factor: float = 1.25, aux_coef: float = 0.01,
              route_block: int = 0):
    """x: (B, S, D) -> (out (B,S,D), aux_loss scalar).

    Routing groups = batch rows (B is the sharded data axis).  With
    ``route_block`` R > 0 capacity competition is further confined to
    R-token blocks within each row (the row end-pads up to a multiple of
    R; pads sit AFTER real tokens, and token-major slot priority means
    they can only take leftover capacity).  Because block boundaries are
    at fixed multiples of R from the row start, routing becomes identical
    whether a prompt is prefilled whole or in chunks whose starts are
    multiples of R — and a single decode token (S == 1) always gets its
    full top-k (one token can't exhaust capacity >= k), so decode routing
    is batch-composition independent either way.
    """
    B, S, D = x.shape
    E = num_experts
    dt = x.dtype
    logits = x @ params["router"].astype(dt)                  # (B,S,E)

    R = route_block
    if R and R > 0 and S > 1:
        nb = -(-S // R)
        pad = nb * R - S
        xg = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        lg = jnp.pad(logits, ((0, 0), (0, pad), (0, 0))) if pad else logits
        out = _dispatch(params, xg.reshape(B * nb, R, D),
                        lg.reshape(B * nb, R, E), E, top_k, capacity_factor)
        out = out.reshape(B, nb * R, D)[:, :S]
    else:
        out = _dispatch(params, x, logits, E, top_k, capacity_factor)

    # Switch-style load-balance auxiliary loss (always on the original
    # unpadded logits so route_block leaves training numerics alone).
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32),
                           axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = aux_coef * E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


# ---------------------------------------------------------------------------
# Sigmoid-routed experts with a choice-only bias and shared experts
# (DeepSeek-V3 ``noaux_tc`` routing), of which this chip holds a share.
#
# The router scores all ``num_experts`` published experts; the layer holds
# experts ``[first_expert, first_expert + held)`` and computes only their
# part of the result.  Held assignments are sorted by expert and run through
# the grouped expert kernel (``kernels/ops.py::gmm``): no capacity, nothing
# dropped.  What the absent experts add is another chip's part.


def _route(params, x, top_k: int, routed_scale: float):
    """x: (T, D) -> (experts (T, k) int32, gates (T, k) f32).  Sigmoid
    scores; the top-k by score plus the bias (held fixed: no gradient);
    gates are the chosen scores normalised to sum 1, times the scale."""
    s = jax.nn.sigmoid((x @ params["router"].astype(x.dtype))
                       .astype(jnp.float32))
    _, experts = jax.lax.top_k(s + jax.lax.stop_gradient(params["bias"]),
                               top_k)
    gates = jnp.take_along_axis(s, experts, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * routed_scale
    return experts, gates


def _held_experts(experts_p, x, local, gates, sizes):
    """The held experts' part of the result, dropless.  x: (T, D); local:
    (T, k) the held expert of each assignment, or ``held`` where it is
    held elsewhere or the token is padding; gates: (T, k); sizes: (held,)
    assignments per held expert.  Returns (out (T, D), dropped)."""
    from repro.kernels import ops
    T, D = x.shape
    k = local.shape[1]
    order = jnp.argsort(local.reshape(-1), stable=True)   # held first
    rows = -(-T * k // 128) * 128                          # kernel's M
    xs = x[jnp.pad(order // k, (0, rows - T * k))]
    h = ops.gmm(xs, experts_p["wi"], sizes)
    g = ops.gmm(xs, experts_p["wg"], sizes)
    y = ops.gmm((jax.nn.silu(g) * h).astype(x.dtype), experts_p["wo"], sizes)
    # back to (token, choice) order; rows past the held ones read zero
    y = y[jnp.argsort(order)].reshape(T, k, D)
    out = jnp.einsum("tkd,tk->td", y, gates.astype(y.dtype))
    return out.astype(x.dtype), jnp.int32(0)


def routed_moe_apply(params, x, valid, *, top_k: int, routed_scale: float,
                     first_expert: int = 0):
    """x: (B, S, D); valid: (B, S) bool, the real tokens (padding is
    routed nowhere).  Returns (out (B, S, D), counters): ``assignments``
    (held,) per held expert, ``offchip`` real assignments to experts held
    elsewhere, ``dropped`` assignments (0: the layer has no capacity)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("moe/route"):
        experts, gates = _route(params, xt, top_k, routed_scale)
        E = params["experts"]["wi"].shape[0]
        real = valid.reshape(B * S, 1)
        held = real & (experts >= first_expert) \
            & (experts < first_expert + E)
        local = jnp.where(held, experts - first_expert, E)
        sizes = jnp.sum(jax.nn.one_hot(local, E, dtype=jnp.int32),
                        axis=(0, 1))
    with jax.named_scope("moe/experts"):
        routed, dropped = _held_experts(params["experts"], xt, local,
                                        jnp.where(held, gates, 0.0), sizes)
    with jax.named_scope("moe/shared"):
        shared = mlp(params["shared"], xt)
    counters = {"assignments": sizes,
                "offchip": jnp.sum(real & ~held).astype(jnp.int32),
                "dropped": dropped}
    return (routed + shared).reshape(B, S, D), counters
