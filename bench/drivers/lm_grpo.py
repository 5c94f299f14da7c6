"""GRPO trainer step for an LLM policy: the compiled step of
``launch/steps.py::make_lm_policy_train_step`` on the latent-attention
MoE stack (``models/transformer.py``), called back to back.

One window unit is one trainer step on one batch of rollouts: the traffic
file's group of rows (a shared prompt, one response per row, right-padded)
with a reward per row, drawn from the seed and the step's index
(``reference/moonlight.py::rollout_batch``).  Every response token is a
trained sample.  The loss and the MoE counters are read two steps behind
the dispatch, so the device always has the next step queued.  Each
dispatch is a ``repro.spans`` span ``lm_pg.step``.
"""
from __future__ import annotations

from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

from benchlib import lm_flops, training_check as tc
from reference import moonlight as RM, policy as RP

UNIT = "step"
IN_FLIGHT = 2

# what the program implements of the DeepSeek-V3 layout
LAYOUT = {"model_type": "deepseek_v3", "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "norm_topk_prob": True,
          "q_lora_rank": None, "n_group": 1, "topk_group": 1,
          "hidden_act": "silu", "moe_layer_freq": 1,
          "attention_bias": False, "tie_word_embeddings": False,
          "rope_scaling": None}


def keys(seed: int):
    """Initialization key and the rollouts' key, both from the seed."""
    base = jax.random.PRNGKey(seed)
    return jax.random.fold_in(base, 1), jax.random.fold_in(base, 2)


def model_config(config: dict):
    """The program's ``ModelConfig`` of the configuration file."""
    from repro.configs.base import ModelConfig
    c = config
    for k, v in LAYOUT.items():
        if c.get(k) != v:
            raise ValueError(f"configuration states {k}={c.get(k)!r}; the "
                             f"program implements {v!r}")
    return ModelConfig(
        name=c["name"], family="moe", source=c["source"],
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        num_experts=c["router_experts"],
        experts_per_token=c["num_experts_per_tok"],
        num_shared_experts=c["n_shared_experts"],
        routed_scale=c["routed_scaling_factor"],
        experts_held=c["n_routed_experts"],
        first_dense_layers=c["first_k_dense_replace"],
        dense_d_ff=c["intermediate_size"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], router_bias_std=c["router_bias_std"])


def norms(tree) -> dict:
    """Each leaf's norm (float64, one leaf on the host at a time), as a
    one-element array: the comparison reads leaves only through their
    norms, and whole float64 copies of the model would not fit the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.array([np.linalg.norm(
        np.asarray(jax.device_get(x), np.float64))]) for p, x in flat}


def delta_norms(tree, host0) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for p, x in flat:
        k = jax.tree_util.keystr(p)
        d = np.asarray(jax.device_get(x), np.float64) - host0[k]
        out[k] = np.array([np.linalg.norm(d)])
    return out


def host_copy(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(jax.device_get(x))
            for p, x in flat}


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.launch.steps import make_lm_policy_train_step
        from repro.models.transformer import init_latent_moe
        from repro.optim import adam_init
        from repro.rl.grpo import GRPOConfig, TokenBatch

        a = traffic["algo"]
        if a["num_epochs"] != 1:
            raise ValueError("the step runs one epoch")
        self.config, self.traffic, self.seed = config, traffic, seed
        cfg = model_config(config)
        self.step_fn = make_lm_policy_train_step(
            cfg, GRPOConfig(
                num_minibatches=a["num_minibatches"], clip_eps=a["clip_eps"],
                lr=a["lr"], beta1=a["beta1"], beta2=a["beta2"], eps=a["eps"],
                max_grad_norm=a["max_grad_norm"], adv_eps=a["adv_eps"]),
            config["first_expert"])
        init_key, self.data_key = keys(seed)
        self.state = list(jax.jit(lambda k: (lambda p: (p, adam_init(p)))(
            init_latent_moe(k, cfg)))(init_key))
        P0, S = traffic["prompt_len"], traffic["seq_len"]

        def batch(key, step):
            tokens, lengths, rewards = RM.rollout_batch(
                key, step, traffic, cfg.vocab_size)
            valid = jnp.arange(S)[None] < lengths[:, None]
            return TokenBatch(tokens, valid,
                              RM.response_mask(lengths, P0, S), rewards)

        self._batch = jax.jit(batch)
        self.widths = RM.widths(config)
        self.samples_per_unit = sum(traffic["response_lens"])
        self.units = 0
        self.attempted = self.failed = 0
        self.dropped = 0
        self.assigned, self.read_steps = 0.0, 0
        self._pending = deque()
        self.first = None

    # ------------------------------------------------------------ window --
    def step(self):
        from repro.spans import span
        with span("lm_pg.step", step=self.units):
            b = self._batch(self.data_key, self.units)
            *self.state, m = self.step_fn(*self.state, b)
        self.units += 1
        self._pending.append(m)
        while len(self._pending) > IN_FLIGHT:
            self._read(self._pending.popleft())

    def _read(self, m):
        m = jax.device_get(m)
        loss = float(m["loss"])
        self.attempted += 1
        self.failed += not np.isfinite(loss)
        self.dropped += int(np.sum(m["dropped"]))
        self.assigned += float(np.sum(m["assignments"]))
        self.read_steps += 1
        return loss

    def sync(self):
        jax.block_until_ready(self.state)
        while self._pending:
            self._read(self._pending.popleft())

    def trained_samples(self) -> int:
        return self.units * self.samples_per_unit

    def first_steps(self, n: int):
        """Set-up drives the step through its first ``n`` steps with the
        window's own call; their readings are kept for the check (host
        copies: the step donates its parameters and Adam state)."""
        params0 = host_copy(self.state[0])
        losses = []
        for k in range(n):
            self.step()
            losses.append(self._pending[-1]["loss"])
            if k == 0:
                moment1 = norms(self.state[1].mu)
        self.sync()
        self.first = tc.FirstSteps([float(x) for x in jax.device_get(
            losses)], moment1, delta_norms(self.state[0], params0))
        self.attempted = self.failed = 0
        self.assigned, self.read_steps = 0.0, 0

    # ----------------------------------------------- readings of the window --
    def _assignments_per_step(self) -> float:
        return self.assigned / max(self.read_steps, 1)

    @property
    def flops_per_sample(self) -> float:
        P0 = self.traffic["prompt_len"]
        resp = self.traffic["response_lens"]
        return lm_flops.step_flops(
            self.widths, [P0 + n for n in resp], resp,
            self._assignments_per_step()) / self.samples_per_unit

    @property
    def kernel_shapes(self) -> dict:
        w = self.widths
        passes = w["moe"] * self.traffic["algo"]["num_minibatches"]
        return {"expert_gmm": {"widths": w, "rows_per_call":
                               self._assignments_per_step() / passes}}

    def end_window(self) -> dict:
        return {"dropped_assignments": float(self.dropped)}

    def release(self):
        self.state = None

    # ------------------------------------------------------------- check --
    def reference(self, dtype="float32", n: int = 3):
        return reference_first_steps(self.config, self.traffic, self.seed,
                                     dtype, n)


def reference_first_steps(config, traffic, seed, dtype="float32",
                          n=3) -> tc.FirstSteps:
    """The plain reference's first ``n`` steps from the seed."""
    w = RM.widths(config)
    init_key, data_key = keys(seed)
    params = RM.init(init_key, w, dtype)
    opt = RP.adam_init(params)
    step = RM.make_step(w, traffic)
    params0, losses = host_copy(params), []
    for k in range(n):
        tokens, lengths, rewards = RM.rollout_batch(data_key, k, traffic,
                                                    w["V"])
        params, opt, loss = step(params, opt, tokens, lengths, rewards)
        losses.append(float(loss))
        if k == 0:
            moment1 = norms(opt["mu"])
    return tc.FirstSteps(losses, moment1, delta_norms(params, params0))
