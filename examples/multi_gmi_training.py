"""End-to-end GMI-DRL driver (the paper's headline workload): synchronized
PPO training across multiple holistic GMIs with

  1. workload-aware selection (Algorithm 2) of (num_env, GMIperGPU),
  2. task-aware TCG_EX layout (holistic serving+training instances),
  3. Algorithm-1 choice of the gradient-reduction schedule,
  4. a few hundred training iterations with global policy sync.

    PYTHONPATH=src python examples/multi_gmi_training.py --iters 200
"""
import argparse
import time

import jax
import numpy as np

from repro.core.placement import plan_tcg_ex_training
from repro.core.selection import explore, make_ppo_profiler
from repro.envs import make_env
from repro.rl.ppo import PPOConfig, init_train, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="Ant")
    ap.add_argument("--num-gpus", type=int, default=2)
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()

    # 1) Algorithm 2: profile-driven configuration search (reduced sweep)
    print("== Algorithm 2: workload-aware GMI selection ==")
    profile = make_ppo_profiler(iters=1, mem_budget_bytes=32e9)
    trace = explore(profile, args.env, num_gpu=args.num_gpus,
                    gmi_per_gpu_range=(2, 1), num_env_sweep=(128, 256, 512))
    num_env, gmi_per_gpu = trace.best_config
    print(f"selected num_env={num_env} GMIperGPU={gmi_per_gpu} "
          f"(projected {trace.best_throughput:,.0f} steps/s, "
          f"{len(trace.points)} profile points)")

    # 2) TCG_EX layout + 3) Algorithm 1 strategy, owned by the layout's
    #    Communicator (repro.comm): mesh + strategy + grad-sync in one
    #    object, re-selectable online from measured reduce times
    layout = plan_tcg_ex_training(
        args.num_gpus, gmi_per_gpu,
        devices=list(range(args.num_gpus * gmi_per_gpu)),
        devices_per_gpu=gmi_per_gpu)
    comm = layout.communicator()
    strat = comm.strategy
    print(layout.manager.summary())
    print(f"Algorithm 1 gradient-reduction strategy: {strat.upper()} "
          f"(grid {comm.grid})")

    # 4) train
    env = make_env(args.env)
    cfg = PPOConfig(num_steps=16, num_epochs=2, num_minibatches=2, lr=1e-3)
    n_inst = len(layout.trainer_gmis)
    step = make_train_step(env, cfg)
    states = []
    for i in range(n_inst):
        p, o, es, ob = init_train(jax.random.key(i), env,
                                  env.spec.policy_dims,
                                  num_envs=num_env // n_inst)
        states.append([p, o, es, ob, jax.random.PRNGKey(i)])

    t0 = time.time()
    total = 0
    for it in range(args.iters):
        rws = []
        for s in states:
            s[0], s[1], s[2], s[3], s[4], m = step(*s)
            rws.append(float(m["reward_mean"]))
        # stage (iii): global policy synchronization across GMIs
        mean_p = jax.tree.map(lambda *xs: sum(xs) / n_inst,
                              *[s[0] for s in states])
        for s in states:
            s[0] = mean_p
        total += cfg.num_steps * num_env
        if it % max(args.iters // 10, 1) == 0:
            print(f"iter {it:4d} reward={np.mean(rws):8.3f} "
                  f"steps/s={total / (time.time() - t0):,.0f}")
    print(f"\ntrained {total:,} env-steps on {n_inst} GMIs "
          f"({strat.upper()} sync) in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
