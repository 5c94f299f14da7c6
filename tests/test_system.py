"""End-to-end behaviour tests for the paper's system (sync PPO with GMI
layouts, async A3C over channels, workload-aware selection, LM training)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.channels import MultiChannelPipeline
from repro.core.placement import plan_async, plan_tcg_ex_training
from repro.envs import make_env
from repro.rl.a3c import actor_collect, staleness, trainer_update
from repro.rl.ppo import PPOConfig, init_train, make_train_step


def test_sync_training_on_tcg_ex_layout():
    """Holistic GMIs (paper Fig 6a): N instances collect + train + sync."""
    layout = plan_tcg_ex_training(2, 2, devices=list(range(4)),
                                  devices_per_gpu=2)
    n_inst = len(layout.trainer_gmis)
    assert layout.reduction_strategy() == "mrr"
    env = make_env("BallBalance")
    cfg = PPOConfig(num_steps=8, num_epochs=1, num_minibatches=1, lr=1e-3)
    step = make_train_step(env, cfg)
    states = []
    for i in range(n_inst):
        p, o, es, ob = init_train(jax.random.key(i), env,
                                  env.spec.policy_dims, num_envs=32)
        states.append([p, o, es, ob, jax.random.PRNGKey(i)])
    for it in range(4):
        for s in states:
            s[0], s[1], s[2], s[3], s[4], m = step(*s)
            assert bool(jnp.isfinite(m["loss"]))
        # stage (iii) global policy synchronization
        mean_p = jax.tree.map(lambda *xs: sum(xs) / n_inst,
                              *[s[0] for s in states])
        for s in states:
            s[0] = mean_p
    # all instances hold identical parameters after sync
    for s in states[1:]:
        for a, b in zip(jax.tree.leaves(states[0][0]),
                        jax.tree.leaves(s[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_a3c_over_channel_pipeline():
    """Decoupled serving/training GMIs (Fig 6b) + MCC experience flow."""
    layout = plan_async(2, 1, 2, devices=list(range(4)), devices_per_gpu=2)
    env = make_env("Ant")
    from repro.models.policy import init_policy
    from repro.optim import adam_init
    params = init_policy(jax.random.key(0), env.spec.policy_dims)
    opt = adam_init(params)
    pipe = MultiChannelPipeline(layout.serving_gmis, layout.trainer_gmis)

    actors = {}
    for a in layout.serving_gmis:
        es, obs = env.reset(jax.random.PRNGKey(a), num_envs=16)
        actors[a] = [es, obs, jax.random.PRNGKey(100 + a)]

    version = jnp.int32(0)
    actor_params = params        # possibly-stale snapshot
    losses = []
    for round_ in range(3):
        for a in layout.serving_gmis:
            es, obs, k = actors[a]
            exp, es, obs, k = actor_collect(actor_params, version, env, es,
                                            obs, k, num_steps=8)
            actors[a] = [es, obs, k]
            pipe.push(a, exp)
        for dst, batches in pipe.flush().items():
            for exp in batches:
                assert int(staleness(version, exp)) >= 0
                params, opt, loss = trainer_update(params, opt, exp)
                losses.append(float(loss))
                version = version + 1
        actor_params = params    # model push (policy parameter sharing)
    assert len(losses) == 3 and all(np.isfinite(losses))
    assert pipe.stats.num_transfers > 0


def test_selection_with_real_profiler_tiny():
    """Algorithm 2 with the real PPO profiler on a tiny search space."""
    from repro.core.selection import explore, make_ppo_profiler
    profile = make_ppo_profiler(iters=1, mem_budget_bytes=32e9)
    trace = explore(profile, "BallBalance", num_gpu=1,
                    gmi_per_gpu_range=(2, 1), num_env_sweep=(128, 256))
    ne, gpg = trace.best_config
    assert ne in (128, 256) and gpg in (1, 2)
    assert trace.best_throughput > 0


def test_lm_training_loss_decreases():
    from repro.configs import get_reduced
    from repro.configs.base import InputShape
    from repro.data import make_batch
    from repro.models import transformer as T
    from repro.optim import adam_init, adam_update

    cfg = get_reduced("granite-moe-1b-a400m")
    shape = InputShape("t", 32, 4, "train")
    params = T.init_model(jax.random.key(0), cfg)
    opt = adam_init(params)

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, cfg, batch, remat=False))(params)
        params, opt = adam_update(grads, opt, params, lr=3e-3, grad_clip=1.0)
        return params, opt, loss

    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape).items()}
    losses = []
    for i in range(15):
        params, opt, loss = step(params, opt, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses


def test_serve_prefill_decode_pipeline():
    from repro.configs import get_reduced
    from repro.models import transformer as T
    cfg = get_reduced("zamba2-7b")
    params = T.init_model(jax.random.key(0), cfg)
    B, P = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, P), 0, cfg.vocab_size)
    logits, caches = T.prefill(params, cfg, {"tokens": toks}, max_seq=P + 8)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(4):
        pos = jnp.full((B,), P + i, jnp.int32)
        logits, caches = T.decode_step(params, cfg, tok, pos, caches)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        assert bool(jnp.all(jnp.isfinite(logits)))
