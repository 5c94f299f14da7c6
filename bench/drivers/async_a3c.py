"""Asynchronous A3C: ``AsyncRunner.round`` from
``launch/steps.py::make_async_runner``, called back to back on the
blocking ring, and ``finish`` after the window.

One window unit is one round: every serving instance rolls ``num_steps``
steps of its envs (with ``megakernel`` the Pallas env megakernel writes
the experience ring slots in place), the ring is flushed to a trainer,
and the trainer takes one A3C update per delivered batch.  Trained samples
are the runner's own ``trained_samples`` counter.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchlib import flops, training_check as tc
from drivers.sync_ppo import check_env
from reference import a3c as RA3C, chain_env, policy as RP

UNIT = "round"


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core.placement import plan_async
        from repro.envs import make_env
        from repro.launch.steps import make_async_runner

        self.config, self.traffic, self.seed = config, traffic, seed
        a = traffic["algo"]
        env = make_env(config["env"]["name"])
        check_env(env, config)
        layout = plan_async(**traffic["layout"])
        if list(layout.serving_gmis) != traffic["serving_gmis"]:
            raise ValueError(f"layout serves from {layout.serving_gmis}, "
                             f"the traffic states {traffic['serving_gmis']}")
        self.runner = make_async_runner(
            env, layout, megakernel=traffic["megakernel"],
            use_fused_kernels=traffic["use_fused_kernels"],
            num_envs=traffic["num_envs"], num_steps=a["num_steps"],
            seed=seed, lr=a["lr"])
        r = self.runner
        if r.overlap or r.env.megakernel != traffic["megakernel"]:
            raise ValueError("runner is not on the blocking ring with the "
                             "producer the traffic states")
        dims = tuple(config["policy_dims"])
        batch = len(traffic["serving_gmis"]) * traffic["num_envs"]
        self.samples_per_unit = a["num_steps"] * batch
        self.flops_per_sample = flops.a3c_per_sample(dims, a["num_steps"])
        self.kernel_shapes = {"nstep": (a["num_steps"], batch)}
        self.units = 0
        self.attempted = self.failed = 0
        self.first = None

    # ------------------------------------------------------------ window --
    def step(self):
        losses, _ = self.runner.round()
        self.units += 1
        self.attempted += len(losses)
        self.failed += sum(not np.isfinite(x) for x in losses)
        return losses

    def sync(self):
        jax.block_until_ready((self.runner.params, self.runner.actors))

    def trained_samples(self) -> int:
        return self.runner.trained_samples

    def first_steps(self, n: int):
        r = self.runner
        params0, losses = r.params, []
        for k in range(n):
            ls = self.step()
            if len(ls) != 1:
                raise ValueError(f"round {k} trained {len(ls)} batches; the "
                                 f"traffic delivers one batch per round")
            losses += ls
            if k == 0:
                moment1 = r.opt_state.mu
        self.sync()
        self.first = tc.first_steps(losses, params0, moment1, r.params)
        self.attempted = self.failed = 0

    def end_window(self) -> dict:
        """Train on whatever the ring still holds; every prediction the
        actors made must have been trained on."""
        losses, _ = self.runner.finish()
        self.attempted += len(losses)
        self.failed += sum(not np.isfinite(x) for x in losses)
        self.sync()
        return {"untrained_samples": float(self.runner.predictions
                                           - self.runner.trained_samples)}

    def release(self):
        self.runner = None

    # ------------------------------------------------------------- check --
    def reference(self, dtype="float32", fault=None, n: int = 3):
        return reference_first_steps(self.config, self.traffic, self.seed,
                                     dtype, fault, n)


def reference_first_steps(config, traffic, seed, dtype="float32",
                          fault=None, n=3) -> tc.FirstSteps:
    """The plain reference's first ``n`` rounds from the seed: each
    serving instance ``g`` resets its envs from key ``seed + g`` and acts
    with key ``seed + 100 + g``; the policy starts from key ``seed``."""
    a = traffic["algo"]
    env = chain_env.ChainEnv(config["env"])
    params = RP.init(jax.random.key(seed), config["policy_dims"], dtype)
    opt = RP.adam_init(params)
    actors = []
    for g in traffic["serving_gmis"]:
        es, obs = env.reset(jax.random.PRNGKey(seed + g), traffic["num_envs"])
        actors.append([es, obs, jax.random.PRNGKey(seed + 100 + g)])
    collect = RA3C.make_collect(env, a["num_steps"])
    train = RA3C.make_train(a, fault)
    params0, actor_params, losses = params, params, []
    for k in range(n):
        exps, boots = [], []
        for actor in actors:
            exp, boot, *rest = collect(actor_params, *actor)
            actor[:] = rest
            exps.append(exp)
            boots.append(boot)
        batch = (*(jnp.concatenate(c, axis=1) for c in zip(*exps)),
                 jnp.concatenate(boots))
        params, opt, loss = train(params, opt, batch)
        actor_params = params
        losses.append(loss)
        if k == 0:
            moment1 = opt["mu"]
    return tc.first_steps(jax.device_get(losses), params0, moment1, params)
