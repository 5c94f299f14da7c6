"""Synchronous PPO: the compiled step of
``launch/steps.py::make_drl_train_step``, called back to back.

One window unit is one PPO iteration: ``num_steps`` policy steps of every
env on the vmap env path, the fused GAE kernel, then ``num_epochs`` x
``num_minibatches`` clipped-surrogate Adam updates.  Every env step is a
trained sample.  Losses are read back two iterations behind the dispatch,
so the device always has the next iteration queued.
"""
from __future__ import annotations

from collections import deque

import numpy as np

import jax

from benchlib import flops, training_check as tc
from reference import chain_env, policy as RP, ppo as RPPO

UNIT = "iteration"
IN_FLIGHT = 2


def keys(seed: int):
    """Initialization key and the step's key, both from the seed."""
    base = jax.random.PRNGKey(seed)
    return base, jax.random.fold_in(base, 1)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.envs import make_env
        from repro.launch.steps import make_drl_train_step
        from repro.models.policy import init_policy
        from repro.optim import adam_init
        from repro.rl.ppo import PPOConfig

        a = traffic["algo"]
        self.config, self.traffic, self.seed = config, traffic, seed
        env = make_env(config["env"]["name"])
        check_env(env, config)
        cfg = PPOConfig(
            num_steps=a["num_steps"], num_epochs=a["num_epochs"],
            num_minibatches=a["num_minibatches"], gamma=a["gamma"],
            lam=a["lam"], clip_eps=a["clip_eps"], vf_coef=a["vf_coef"],
            ent_coef=a["ent_coef"], lr=a["lr"],
            max_grad_norm=a["max_grad_norm"],
            use_fused_kernels=traffic["use_fused_kernels"])
        self.step_fn, _ = make_drl_train_step(env, cfg)
        n = traffic["num_envs"]
        init_key, step_key = keys(seed)
        dims = tuple(config["policy_dims"])
        # rl/ppo.py::init_train's split, with the weights and optimizer
        # state made on the device in one jitted call; the env reset stays
        # eager, as the program runs it (jitted whole, it crashes the TPU
        # compiler: PERF.md, Open questions)
        kp, ke = jax.random.split(init_key)
        params, opt = jax.jit(
            lambda k: (lambda p: (p, adam_init(p)))(init_policy(k, dims)))(kp)
        self.state = [params, opt, *env.reset(ke, n), step_key]
        self.samples_per_unit = a["num_steps"] * n
        self.flops_per_sample = flops.sync_ppo_per_sample(
            dims, a["num_steps"], a["num_epochs"])
        self.kernel_shapes = {}
        self.units = 0
        self.attempted = self.failed = 0
        self._pending = deque()
        self.first = None

    # ------------------------------------------------------------ window --
    def step(self):
        *self.state, m = self.step_fn(*self.state)
        self.units += 1
        self._pending.append(m["loss"])
        while len(self._pending) > IN_FLIGHT:
            self._read(self._pending.popleft())

    def _read(self, loss):
        loss = float(loss)
        self.attempted += 1
        self.failed += not np.isfinite(loss)
        return loss

    def sync(self):
        jax.block_until_ready(self.state)
        while self._pending:
            self._read(self._pending.popleft())

    def trained_samples(self) -> int:
        return self.units * self.samples_per_unit

    def first_steps(self, n: int):
        """Set-up drives the step through its first ``n`` iterations with
        the window's own call; their readings are kept for the check."""
        params0 = self.state[0]
        losses = []
        for k in range(n):
            self.step()
            losses.append(self._pending[-1])
            if k == 0:
                moment1 = self.state[1].mu
        self.sync()
        self.first = tc.first_steps(jax.device_get(losses), params0,
                                    moment1, self.state[0])
        self.attempted = self.failed = 0

    def end_window(self) -> dict:
        return {}

    def release(self):
        self.state = None

    # ------------------------------------------------------------- check --
    def reference(self, dtype="float32", fault=None, n: int = 3):
        return reference_first_steps(self.config, self.traffic, self.seed,
                                     dtype, fault, n)


def check_env(env, config):
    spec = env.spec
    got = (spec.obs_dim, spec.act_dim, tuple(spec.policy_dims),
           spec.substeps, spec.max_episode_len)
    e = config["env"]
    want = (e["obs_dim"], e["act_dim"], tuple(config["policy_dims"]),
            e["substeps"], e["max_episode_len"])
    if got != want:
        raise ValueError(f"program env {spec.name} is {got}, the "
                         f"configuration states {want}")


def reference_first_steps(config, traffic, seed, dtype="float32",
                          fault=None, n=3) -> tc.FirstSteps:
    """The plain reference's first ``n`` iterations from the seed."""
    a = traffic["algo"]
    env = chain_env.ChainEnv(config["env"])
    init_key, key = keys(seed)
    kp, ke = jax.random.split(init_key)
    params = RP.init(kp, config["policy_dims"], dtype)
    opt = RP.adam_init(params)
    env_state, obs = env.reset(ke, traffic["num_envs"])
    iterate = RPPO.make_iteration(env, a, fault)
    params0, losses = params, []
    for k in range(n):
        params, opt, env_state, obs, key, loss = iterate(
            params, opt, env_state, obs, key)
        losses.append(loss)
        if k == 0:
            moment1 = opt["mu"]
    return tc.first_steps(jax.device_get(losses), params0, moment1, params)
