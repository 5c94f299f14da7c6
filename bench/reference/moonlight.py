"""Reference of Moonlight-16B-A3B (DeepSeek-V3 layout) as one chip's
share of an expert-parallel deployment, and of one GRPO step on it.

The layers as the published config describes them: pre-norm blocks of
latent attention (MLA, no query LoRA: ``q = x W_q`` split into 128 + 64
per head; ``[c, k_r] = x W_kva``, ``c`` RMS-normed; ``[k_nope, v] =
c W_kvb``; one rotated ``k_r`` shared by the heads; causal softmax of
``(q_nope k_nope + q_r k_r) / sqrt(192)``), then a SiLU-gated MLP (the
leading dense layers) or the experts: sigmoid scores over every routed
expert, the top-k chosen by score plus a fixed bias, gates the chosen
scores normalised and scaled, plus the shared experts.  This chip holds
experts ``[first_expert, first_expert + held)``: each is computed densely
over every token and weighted by its gate where the token chose it (a
0/1 routing mask), and the experts held elsewhere add nothing.  The
softmax is full over a row's keys, computed one block of queries at a
time so that a row of 8192 fits.

A GRPO step: advantages ``(r - mean) / (std + eps)`` over the group
(sample std), the old log-probabilities from the step's starting
parameters, then per minibatch the token mean over its response tokens of
``-min(r A, clip(r, 1 - eps, 1 + eps) A)`` with ``r = exp(logp - old)``,
its gradient summed one row at a time, and Adam with the global norm
clipped (``reference/policy.py``).

Weights are drawn leaf by leaf from the seed in the pytree's flattening
order (matrices ``N(0, 1/fan_in)``, the embedding ``N(0, 1)``, the bias
``N(0, router_bias_std^2)``, norm scales ones), the rollouts by
:func:`rollout_batch`.  ``dtype`` float32 computes every product at
HIGHEST; bfloat16 is the control.  Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import policy as P

QUERY_BLOCK = 512


def widths(config: dict) -> dict:
    c = config
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        dn=c["qk_nope_head_dim"], dr=c["qk_rope_head_dim"],
        dv=c["v_head_dim"], r=c["kv_lora_rank"], V=c["vocab_size"],
        F=c["moe_intermediate_size"], Fd=c["intermediate_size"],
        Fs=c["n_shared_experts"] * c["moe_intermediate_size"],
        E=c["router_experts"], Eh=c["n_routed_experts"],
        k=c["num_experts_per_tok"], scale=c["routed_scaling_factor"],
        dense=c["first_k_dense_replace"],
        moe=c["num_hidden_layers"] - c["first_k_dense_replace"],
        theta=float(c["rope_theta"]), eps=c["rms_norm_eps"],
        bias_std=c["router_bias_std"], first=c["first_expert"])


def shapes(w: dict) -> dict:
    def layer(n, ffn):
        one = {"ln1": {"scale": (w["D"],)}, "ln2": {"scale": (w["D"],)},
               "mla": {"wq": {"w": (w["D"], w["H"] * (w["dn"] + w["dr"]))},
                       "wkv_a": {"w": (w["D"], w["r"] + w["dr"])},
                       "kv_norm": {"scale": (w["r"],)},
                       "wkv_b": {"w": (w["r"], w["H"] * (w["dn"] + w["dv"]))},
                       "wo": {"w": (w["H"] * w["dv"], w["D"])}}, **ffn}
        return jax.tree.map(lambda s: (n,) + s, one,
                            is_leaf=lambda s: isinstance(s, tuple))

    D, F, Fs, Fd = w["D"], w["F"], w["Fs"], w["Fd"]
    return {
        "embed": {"table": (w["V"], D)},
        "dense": layer(w["dense"], {"mlp": {"wi": (D, Fd), "wg": (D, Fd),
                                            "wo": (Fd, D)}}),
        "layers": layer(w["moe"], {"moe": {
            "router": (D, w["E"]), "bias": (w["E"],),
            "experts": {"wi": (w["Eh"], D, F), "wg": (w["Eh"], D, F),
                        "wo": (w["Eh"], F, D)},
            "shared": {"wi": (D, Fs), "wg": (D, Fs), "wo": (Fs, D)}}}),
        "final_norm": {"scale": (D,)},
        "unembed": {"w": (D, w["V"])},
    }


def init(key, w: dict, dtype="float32"):
    paths, tree = jax.tree_util.tree_flatten_with_path(
        shapes(w), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        if name == "scale":
            out.append(jnp.ones(shape, jnp.float32))
            continue
        if name == "table":
            std = 1.0
        elif name == "bias":
            std = w["bias_std"]
        else:
            std = shape[-2] ** -0.5
        out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std)
    return jax.tree.map(lambda x: x.astype(dtype),
                        jax.tree_util.tree_unflatten(tree, out))


# ------------------------------------------------------------- the model --
def _mm(a, b):
    prec = P.precision_of(b.dtype)
    return jnp.matmul(a.astype(b.dtype), b, precision=prec)


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (S, heads, d), pairs (i, i + d/2) rotated by position * theta^(-2i/d)."""
    S, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :d // 2].astype(jnp.float32), x[..., d // 2:].astype(
        jnp.float32)
    out = jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                           a * jnp.sin(ang) + b * jnp.cos(ang)], -1)
    return out.astype(x.dtype)


def mla(p, x, w):
    """One row: x (S, D) -> (S, D)."""
    S = x.shape[0]
    H, dn, dr, dv = w["H"], w["dn"], w["dr"], w["dv"]
    q = _mm(x, p["wq"]["w"]).reshape(S, H, dn + dr)
    kva = _mm(x, p["wkv_a"]["w"])
    c = _rms(kva[:, :w["r"]], p["kv_norm"]["scale"], w["eps"])
    kv = _mm(c, p["wkv_b"]["w"]).reshape(S, H, dn + dv)
    q_nope, q_r = q[..., :dn], _rope(q[..., dn:], w["theta"])
    k_r = _rope(kva[:, None, w["r"]:], w["theta"])[:, 0]     # (S, dr)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    prec = P.precision_of(x.dtype)
    qb = min(QUERY_BLOCK, S)

    @jax.checkpoint
    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * qb, qb)  # noqa
        s = (jnp.einsum("qhd,khd->hqk", sl(q_nope), k_nope, precision=prec)
             + jnp.einsum("qhd,kd->hqk", sl(q_r), k_r, precision=prec))
        s = s.astype(jnp.float32) / jnp.sqrt(float(dn + dr))
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr.astype(v.dtype), v,
                          precision=prec)

    out = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, H * dv)
    return _mm(out, p["wo"]["w"])


def _ffn(p, x):
    return _mm(jax.nn.silu(_mm(x, p["wg"])) * _mm(x, p["wi"]), p["wo"])


def moe(p, x, w):
    """One row: x (S, D) -> (S, D), the held experts' part plus the
    shared experts."""
    s = jax.nn.sigmoid(_mm(x, p["router"]).astype(jnp.float32))
    _, top = jax.lax.top_k(s + p["bias"].astype(jnp.float32), w["k"])
    g = jnp.take_along_axis(s, top, -1)
    g = g / g.sum(-1, keepdims=True) * w["scale"]
    chose = jax.nn.one_hot(top, w["E"], dtype=jnp.float32)   # (S, k, E)
    gate = jnp.einsum("ske,sk->se", chose, g)[:, w["first"]:
                                                 w["first"] + w["Eh"]]
    routed = 0.0
    for i in range(w["Eh"]):         # one held expert at a time: fits
        e = jax.tree.map(lambda a: a[i], p["experts"])
        routed = routed + _ffn(e, x) * gate[:, i:i + 1].astype(x.dtype)
    return routed + _ffn(p["shared"], x)


def _layer(lp, x, w):
    x = x + mla(lp["mla"], _rms(x, lp["ln1"]["scale"], w["eps"]), w)
    h = _rms(x, lp["ln2"]["scale"], w["eps"])
    return x + (moe(lp["moe"], h, w) if "moe" in lp else _ffn(lp["mlp"], h))


def hidden(params, tokens, w):
    """One row of token ids (S,) -> final-normed hidden (S, D)."""
    x = params["embed"]["table"][tokens]
    for group in ("dense", "layers"):
        stack = params[group]
        n = jax.tree.leaves(stack)[0].shape[0]
        for i in range(n):
            x = jax.checkpoint(lambda lp, x: _layer(lp, x, w))(
                jax.tree.map(lambda a: a[i], stack), x)
    return _rms(x, params["final_norm"]["scale"], w["eps"])


def logprobs(params, tokens, w):
    """One row: log-probability of each next token, (S - 1,)."""
    h = hidden(params, tokens, w)[:-1]
    logits = _mm(h, params["unembed"]["w"]).astype(jnp.float32)
    picked = jnp.take_along_axis(logits, tokens[1:, None], -1)[:, 0]
    return picked - jax.nn.logsumexp(logits, -1)


# ------------------------------------------------------------- the data --
def rollout_batch(key, step, traffic: dict, vocab: int):
    """The rollouts of step ``step`` (an int, or a traced one) from the
    data key: one group of ``len(response_lens)`` rows of ``seq_len``
    positions, each the group's shared prompt of ``prompt_len`` tokens, a
    response of one of the lengths (their order drawn), then padding (id
    0); ids uniform over the vocabulary slice; one N(0, 1) reward per row.
    Returns (tokens (G, S) int32, lengths (G,) int32 of prompt +
    response, rewards (G,) f32)."""
    G, S = len(traffic["response_lens"]), traffic["seq_len"]
    P0 = traffic["prompt_len"]
    kp, kr, ko, kw = jax.random.split(jax.random.fold_in(key, step), 4)
    lengths = P0 + jax.random.permutation(
        ko, jnp.asarray(traffic["response_lens"], jnp.int32))
    prompt = jax.random.randint(kp, (P0,), 0, vocab, jnp.int32)
    tokens = jax.random.randint(kr, (G, S), 0, vocab, jnp.int32)
    pos = jnp.arange(S)[None]
    tokens = tokens.at[:, :P0].set(prompt[None])
    tokens = jnp.where(pos < lengths[:, None], tokens, 0)
    return tokens, lengths, jax.random.normal(kw, (G,), jnp.float32)


def response_mask(lengths, prompt_len: int, seq_len: int):
    """(G, S - 1): 1 where the next token tokens[:, t + 1] is a response
    token."""
    t = jnp.arange(1, seq_len)[None]
    return ((t >= prompt_len) & (t < lengths[:, None])).astype(jnp.float32)


# ------------------------------------------------------------- the step --
def make_step(w: dict, traffic: dict):
    """Returns ``step(params, opt, tokens, lengths, rewards) -> (params,
    opt, loss)``: jitted pieces driven one row at a time."""
    a = traffic["algo"]
    M = a["num_minibatches"]
    adam = dict(lr=a["lr"], beta1=a["beta1"], beta2=a["beta2"],
                eps=a["eps"], clip=a["max_grad_norm"])

    old_logp = jax.jit(lambda p, t: logprobs(p, t, w))

    def row_loss(p, tokens, old, resp, adv):
        ratio = jnp.exp(logprobs(p, tokens, w) - old)
        eps = a["clip_eps"]
        pg = -jnp.minimum(ratio * adv,
                          jnp.clip(ratio, 1 - eps, 1 + eps) * adv)
        return jnp.sum(pg * resp)

    # the gradient sum and the state are updated in place (donated): a
    # second copy of either would not fit one chip at the cell's size
    row_grad = jax.jit(
        lambda p, acc, *row: (lambda lv, g: (lv, jax.tree.map(
            jnp.add, acc, g)))(*jax.value_and_grad(row_loss)(p, *row)),
        donate_argnums=(1,))
    update = jax.jit(
        lambda g, n, o, p: P.adam(jax.tree.map(lambda x: x / n, g), o, p,
                                  **adam), donate_argnums=(0, 2, 3))

    def step(params, opt, tokens, lengths, rewards):
        G = tokens.shape[0]
        adv = (rewards - rewards.mean()) / (
            jnp.std(rewards, ddof=1) + a["adv_eps"])
        resp = response_mask(lengths, traffic["prompt_len"],
                             traffic["seq_len"])
        olds = [old_logp(params, tokens[i]) for i in range(G)]
        losses = []
        per = G // M
        for m in range(M):
            rows = range(m * per, (m + 1) * per)
            n = sum(float(resp[i].sum()) for i in rows)
            total = 0.0
            grads = jax.tree.map(jnp.zeros_like, params)
            for i in rows:
                lv, grads = row_grad(params, grads, tokens[i], olds[i],
                                     resp[i], adv[i])
                total += lv
            n = max(n, 1.0)
            params, opt = update(grads, n, opt, params)
            losses.append(total / n)
        return params, opt, jnp.mean(jnp.stack(losses))

    return step
