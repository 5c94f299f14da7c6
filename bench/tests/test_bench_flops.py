"""The benchmark's policy-FLOP function against the dot FLOPs XLA compiles
(``launch/hlo_analysis.analyze``, loop-aware), at Table-6 widths and a
tiny env count on the CPU.

XLA lowers one product of the algorithm without a dot: the value head's
input gradient, an outer product (contraction size 1), becomes an
elementwise multiply, so the compiled count lacks ``2 * hidden`` FLOPs per
sample and training pass.  The env's own products (the sensor projection)
are counted from a rollout of the env alone."""
import functools
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

from benchlib import flops                               # noqa: E402

T, N, S = 4, 64, 2
ENVS = ["ShadowHand", "Ant"]


def dot_flops(fn, *args):
    from repro.launch.hlo_analysis import analyze
    return analyze(jax.jit(fn).lower(*args).compile().as_text())["dot_flops"]


def outer_product(dims) -> int:
    """The value head's input gradient, which XLA compiles as a multiply."""
    return 2 * dims[-2]


def env_rollout_flops(env, n, steps):
    """Dot FLOPs of ``steps`` env steps alone (the sensor projection)."""
    es, _ = env.reset(jax.random.PRNGKey(0), n)
    acts = jax.random.normal(jax.random.PRNGKey(1),
                             (steps, n, env.spec.act_dim))

    def roll(es, acts):
        return jax.lax.scan(lambda s, a: env.step(s, a)[:2], es, acts)
    return dot_flops(roll, es, acts)


@pytest.mark.parametrize("name", ENVS)
def test_sync_ppo_step(name):
    from repro.envs import make_env
    from repro.launch.steps import make_drl_train_step
    from repro.rl.ppo import PPOConfig, init_train
    env = make_env(name)
    dims = env.spec.policy_dims
    cfg = PPOConfig(num_steps=T, use_fused_kernels=True)
    step, _ = make_drl_train_step(env, cfg)
    state = init_train(jax.random.PRNGKey(0), env, dims, N)
    from repro.launch.hlo_analysis import analyze
    got = analyze(step.lower(*state, jax.random.PRNGKey(1)).compile()
                  .as_text())["dot_flops"]
    want = T * N * (flops.sync_ppo_per_sample(dims, T, cfg.num_epochs)
                    - cfg.num_epochs * outer_product(dims)) \
        + env_rollout_flops(env, N, T)
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", ENVS)
def test_a3c_trainer_update_and_acting(name):
    from repro.envs import make_env
    from repro.models.policy import init_policy, policy_apply
    from repro.optim import adam_init
    from repro.rl.a3c import Experience, trainer_update
    env = make_env(name)
    dims = env.spec.policy_dims
    params = init_policy(jax.random.key(0), dims)
    O, A = env.spec.obs_dim, env.spec.act_dim
    exp = Experience(obs=jnp.ones((T, S * N, O)),
                     actions=jnp.ones((T, S * N, A)),
                     rewards=jnp.ones((T, S * N)), dones=jnp.zeros((T, S * N)),
                     bootstrap=jnp.ones((S * N,)), actor_version=jnp.int32(0))
    train = dot_flops(functools.partial(trainer_update, use_fused_kernels=True),
                      params, adam_init(params), exp)
    obs = jnp.ones((N, O))
    act = dot_flops(policy_apply, params, obs)
    boot = dot_flops(lambda p, o: policy_apply(p, o)[2], params, obs)
    got = train + S * (T * act + boot)
    assert got == pytest.approx(
        T * S * N * (flops.a3c_per_sample(dims, T) - outer_product(dims)),
        rel=1e-9)


def test_bytes():
    assert flops.nstep_bytes(16, 128) == 4 * (3 * 16 * 128 + 128)
