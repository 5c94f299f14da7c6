"""GRPO-style clipped-surrogate step for a token policy (an LLM trained
by RL on its own rollouts; DeepSeekMath, arXiv:2402.03300, without the KL
term).

A batch is one group of G rows, each the group's shared prompt plus one
sampled response, right-padded to one length, with one scalar reward per
row.  The advantage of every response token of a row is the row's reward
standardised over the group.  One step: the old log-probabilities from a
forward pass of the step's starting parameters over every row, then one
epoch of ``num_minibatches`` Adam updates, each on the token mean over its
rows' response tokens of PPO's clipped surrogate (``rl/ppo.py``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.transformer import token_logprobs
from repro.optim import AdamState, adam_update
from repro.rl.ppo import clipped_surrogate


class GRPOConfig(NamedTuple):
    num_minibatches: int = 2
    clip_eps: float = 0.2
    lr: float = 1e-6
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    max_grad_norm: float = 1.0
    adv_eps: float = 1e-6


class TokenBatch(NamedTuple):
    tokens: jax.Array      # (G, S) int32: prompt + response + padding
    valid: jax.Array       # (G, S) bool: prompt and response tokens
    response: jax.Array    # (G, S - 1) f32: 1 where tokens[:, 1:] is a
    #                        response token (the ones trained)
    rewards: jax.Array     # (G,) f32


def group_advantages(rewards, eps: float):
    """(r - mean) / (std + eps) over the group (sample std, as TRL's GRPO
    takes it)."""
    return (rewards - rewards.mean()) / (jnp.std(rewards, ddof=1) + eps)


def lm_pg_loss(params, model_cfg, tokens, valid, response, old_logp, advs,
               clip_eps: float, first_expert: int = 0):
    """Token mean over the response tokens of the clipped surrogate.
    Returns (loss, MoE counters)."""
    logp, counters = token_logprobs(params, model_cfg, tokens, valid,
                                    first_expert)
    pg = clipped_surrogate(jnp.exp(logp - old_logp), advs[:, None],
                           clip_eps)
    return jnp.sum(pg * response) / jnp.maximum(jnp.sum(response), 1.0), \
        counters


def train_step(params, opt_state: AdamState, batch: TokenBatch, cfg:
               GRPOConfig, model_cfg, first_expert: int = 0):
    """One GRPO step.  Returns (params, opt_state, metrics): the mean
    minibatch loss, the response tokens trained, and the counters of the
    training passes summed over minibatches: the MoE counters (per MoE
    layer) and ``attn_blocks`` / ``attn_blocks_full``, the attention block
    pairs computed and those of the full squares (summed over layers; each
    minibatch's recompute, backward and old log-probabilities compute the
    same blocks again)."""
    M = cfg.num_minibatches
    advs = group_advantages(batch.rewards, cfg.adv_eps)
    mb = jax.tree.map(lambda x: x.reshape((M, x.shape[0] // M)
                                          + x.shape[1:]),
                      (batch.tokens, batch.valid, batch.response, advs))
    with jax.named_scope("lm_pg/old_logp"):
        old = jax.lax.map(lambda b: token_logprobs(
            params, model_cfg, b[0], b[1], first_expert)[0], mb[:2])

    losses, counters = [], []
    # unrolled (M is small): a scan would carry, and double-buffer, the
    # parameters and the Adam state
    for m in range(M):
        tokens, valid, response, adv = (x[m] for x in mb)
        with jax.named_scope("lm_pg/loss_grad"):
            (loss, c), grads = jax.value_and_grad(
                lm_pg_loss, has_aux=True)(
                    params, model_cfg, tokens, valid, response, old[m],
                    adv, cfg.clip_eps, first_expert)
        with jax.named_scope("lm_pg/adam"):
            params, opt_state = adam_update(
                grads, opt_state, params, lr=cfg.lr, beta1=cfg.beta1,
                beta2=cfg.beta2, eps=cfg.eps, grad_clip=cfg.max_grad_norm)
        losses.append(loss)
        counters.append(c)
    metrics = {"loss": jnp.mean(jnp.stack(losses)),
               "tokens": jnp.sum(batch.response),
               **jax.tree.map(lambda *c: sum(c), *counters)}
    return params, opt_state, metrics
