import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.envs import make_env
from repro.rl.a3c import Experience, nstep_returns, staleness
from repro.rl.ppo import PPOConfig, init_train, make_train_step, ppo_loss
from repro.rl.rollout import collect, gae


def _naive_gae(rewards, values, dones, last_value, gamma, lam):
    T, N = rewards.shape
    advs = np.zeros((T, N), np.float32)
    adv = np.zeros(N, np.float32)
    v_next = np.asarray(last_value)
    for t in reversed(range(T)):
        nonterm = 1.0 - dones[t]
        delta = rewards[t] + gamma * v_next * nonterm - values[t]
        adv = delta + gamma * lam * nonterm * adv
        advs[t] = adv
        v_next = values[t]
    return advs


def test_gae_matches_naive_loop():
    key = jax.random.key(0)
    T, N = 12, 5
    ks = jax.random.split(key, 4)
    rewards = jax.random.normal(ks[0], (T, N))
    values = jax.random.normal(ks[1], (T, N))
    dones = (jax.random.uniform(ks[2], (T, N)) < 0.2).astype(jnp.float32)
    last_value = jax.random.normal(ks[3], (N,))
    advs, rets = gae(rewards, values, dones, last_value, 0.99, 0.95)
    want = _naive_gae(np.asarray(rewards), np.asarray(values),
                      np.asarray(dones), last_value, 0.99, 0.95)
    np.testing.assert_allclose(np.asarray(advs), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rets), want + np.asarray(values),
                               rtol=1e-5, atol=1e-5)


def test_gae_lambda1_equals_mc_returns():
    T, N = 8, 3
    rewards = jnp.ones((T, N))
    values = jnp.zeros((T, N))
    dones = jnp.zeros((T, N))
    last_value = jnp.zeros((N,))
    advs, rets = gae(rewards, values, dones, last_value, gamma=1.0, lam=1.0)
    want = jnp.arange(T, 0, -1)[:, None] * jnp.ones((T, N))
    np.testing.assert_allclose(np.asarray(rets), np.asarray(want), rtol=1e-6)


def test_nstep_returns_bootstrap():
    rewards = jnp.zeros((3, 2))
    dones = jnp.zeros((3, 2))
    boot = jnp.array([1.0, 2.0])
    rets = nstep_returns(rewards, dones, boot, gamma=0.5)
    np.testing.assert_allclose(np.asarray(rets[0]), [0.125, 0.25], rtol=1e-6)


def test_ppo_improves_on_ballbalance():
    env = make_env("BallBalance")
    cfg = PPOConfig(num_steps=16, num_epochs=2, num_minibatches=2, lr=1e-3)
    params, opt, est, obs = init_train(jax.random.key(0), env,
                                       env.spec.policy_dims, num_envs=128)
    step = make_train_step(env, cfg)
    k = jax.random.PRNGKey(0)
    rewards = []
    for _ in range(25):
        params, opt, est, obs, k, m = step(params, opt, est, obs, k)
        rewards.append(float(m["reward_mean"]))
    assert all(np.isfinite(rewards))
    assert np.mean(rewards[-5:]) > np.mean(rewards[:5]), rewards


def test_ppo_fused_kernels_improve_and_match_metric_shapes():
    """use_fused_kernels=True must train (reward goes up) and produce the
    exact metric tree of the unfused path."""
    env = make_env("BallBalance")
    base = PPOConfig(num_steps=16, num_epochs=2, num_minibatches=2, lr=1e-3)
    fused = base._replace(use_fused_kernels=True)
    params, opt, est, obs = init_train(jax.random.key(0), env,
                                       env.spec.policy_dims, num_envs=128)
    step_f = make_train_step(env, fused)
    k = jax.random.PRNGKey(0)
    rewards = []
    for _ in range(25):
        params, opt, est, obs, k, mf = step_f(params, opt, est, obs, k)
        rewards.append(float(mf["reward_mean"]))
    assert all(np.isfinite(rewards))
    assert np.mean(rewards[-5:]) > np.mean(rewards[:5]), rewards

    p2, o2, e2, ob2 = init_train(jax.random.key(1), env,
                                 env.spec.policy_dims, num_envs=128)
    step_u = make_train_step(env, base)
    *_, mu = step_u(p2, o2, e2, ob2, jax.random.PRNGKey(1))
    assert set(mf) == set(mu)
    assert all(mf[k_].shape == mu[k_].shape and mf[k_].dtype == mu[k_].dtype
               for k_ in mf)


def test_async_runner_fused_nstep_trains():
    """use_fused_kernels routes the trainer's n-step returns through the
    fused Pallas scan; training must stay finite and lossless."""
    from repro.rl.a3c import AsyncRunner
    env = make_env("Ant")
    runner = AsyncRunner(env, [0, 1], [100, 101],
                         gmi_gpu={0: 0, 1: 1, 100: 0, 101: 1},
                         num_envs=16, num_steps=8, use_fused_kernels=True)
    losses = []
    for _ in range(3):
        ls, stale = runner.round()
        losses += ls
    assert losses and all(np.isfinite(losses))
    assert runner.trained_samples == runner.predictions


def test_async_runner_over_ring_pipeline():
    from repro.rl.a3c import AsyncRunner
    env = make_env("Ant")
    runner = AsyncRunner(env, [0, 1], [100, 101],
                         gmi_gpu={0: 0, 1: 1, 100: 0, 101: 1},
                         num_envs=16, num_steps=8)
    losses = []
    for _ in range(3):
        ls, stale = runner.round()
        losses += ls
        assert all(s >= 0 for s in stale)
    assert losses and all(np.isfinite(losses))
    assert runner.trained_samples == runner.predictions  # nothing dropped
    # per-group routing fed BOTH trainers each flush
    assert runner.pipe.migrator.load[100] == runner.pipe.migrator.load[101]


@pytest.mark.parametrize("megakernel", [False, True],
                         ids=["collect", "collect_ring"])
def test_async_runner_traces_its_update_once_at_a_fixed_shape(megakernel):
    """Every round's batches reuse the runner's one compiled update."""
    from repro.core.placement import plan_async
    from repro.launch.steps import make_async_runner
    layout = plan_async(2, 1, 2, devices=list(range(4)), devices_per_gpu=2)
    runner = make_async_runner(make_env("Ant"), layout, num_envs=16,
                               num_steps=8, megakernel=megakernel)
    for _ in range(3):
        ls, _ = runner.round()
        assert ls and all(np.isfinite(ls))
    assert runner.update_traces == 1


@pytest.mark.parametrize("fused", [False, True], ids=["scan", "fused"])
def test_async_runner_compiled_update_matches_eager_trainer_update(fused):
    from repro.rl.a3c import AsyncRunner, actor_collect, trainer_update
    env = make_env("Ant")
    runner = AsyncRunner(env, [0], [100], num_envs=16, num_steps=8,
                         lr=1e-3, use_fused_kernels=fused)
    es, obs = env.reset(jax.random.PRNGKey(3), num_envs=16)
    exp, *_ = actor_collect(runner.params, runner.version, env, es, obs,
                            jax.random.PRNGKey(4), 8)
    got = runner._update(runner.params, runner.opt_state, exp)
    want = trainer_update(runner.params, runner.opt_state, exp,
                          lr=1e-3, use_fused_kernels=fused)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-6)
    assert runner.update_traces == 1


def test_async_runner_replan_to_new_num_envs_traces_once_more():
    from repro.core.controller import Decision
    from repro.core.placement import plan_async
    from repro.launch.steps import make_async_runner
    layout = plan_async(2, 1, 2, devices=list(range(4)), devices_per_gpu=2)
    runner = make_async_runner(make_env("Ant"), layout, num_envs=16,
                               num_steps=8)
    runner.layout_builder = lambda d: plan_async(
        2, d.serving_gpus, d.gmi_per_gpu, devices=list(range(4)),
        devices_per_gpu=2)
    runner.round()
    runner.round()
    assert runner.update_traces == 1
    runner.replan(Decision(num_env=8, gmi_per_gpu=2, serving_gpus=1,
                           reason="test"))
    assert runner.num_envs == 8
    for _ in range(2):
        ls, _ = runner.round()
        assert ls and all(np.isfinite(ls))
    assert runner.update_traces == 2
    assert runner.trained_samples == runner.predictions


def test_collect_shapes_and_logprob_consistency():
    from repro.models.policy import init_policy, log_prob, policy_apply
    env = make_env("Ant")
    params = init_policy(jax.random.key(1), env.spec.policy_dims)
    est, obs = env.reset(jax.random.PRNGKey(0), num_envs=8)
    traj, est, obs2, last_v, _ = collect(params, env, est, obs,
                                         jax.random.PRNGKey(2), 6)
    assert traj.obs.shape == (6, 8, env.spec.obs_dim)
    assert traj.actions.shape == (6, 8, env.spec.act_dim)
    mu, log_std, v = policy_apply(params, traj.obs)
    lp = log_prob(mu, log_std, traj.actions)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(traj.log_probs),
                               rtol=1e-4, atol=1e-4)


def test_staleness_counter():
    exp = Experience(obs=jnp.zeros((1, 1, 2)), actions=jnp.zeros((1, 1, 1)),
                     rewards=jnp.zeros((1, 1)), dones=jnp.zeros((1, 1)),
                     bootstrap=jnp.zeros((1,)), actor_version=jnp.int32(3))
    assert int(staleness(jnp.int32(7), exp)) == 4


# ------------------------------------------- packed minibatch shuffling --
class _SplitObs:
    """An env whose observations carry an extra trailing dim: each
    ``(N, D)`` observation of ``env`` is served as ``(N, rows, D / rows)``."""

    def __init__(self, env, rows: int):
        self.env, self.rows, self.spec = env, rows, env.spec

    def reset(self, key, num_envs: int):
        state, obs = self.env.reset(key, num_envs)
        return state, obs.reshape(num_envs, self.rows, -1)

    def step(self, state, action):
        state, obs, reward, done = self.env.step(state, action)
        return state, obs.reshape(obs.shape[0], self.rows, -1), reward, done


def _flat_policy(params, obs):
    from repro.models.policy import policy_apply
    return policy_apply(params, obs.reshape(obs.shape[:-2] + (-1,)))


def _packing_env(case):
    """(env, policy_fn) for each obs case: table widths 30 and 234, neither
    a multiple of 128, and an obs with an extra trailing dim."""
    from repro.models.policy import policy_apply
    if case == "split_obs":
        return _SplitObs(make_env("BallBalance"), 4), _flat_policy
    return make_env({"flat_24": "BallBalance",
                     "flat_211": "ShadowHand"}[case]), policy_apply


_PACK_CASES = ["flat_24", "split_obs", "flat_211"]


def _per_array_pack(fields):
    """The per-array shuffle: every trajectory field is its own table and
    is row-gathered on its own."""
    return list(fields), tuple


@pytest.mark.parametrize("fused", [False, True], ids=["gae", "gae_fused"])
@pytest.mark.parametrize("case", _PACK_CASES)
def test_packed_gather_yields_the_per_array_minibatches(case, fused):
    from repro.models.policy import init_policy
    from repro.rl.ppo import pack_rows
    from repro.rl.rollout import gae_fused
    T, N, M = 4, 16, 4
    env, policy_fn = _packing_env(case)
    params = init_policy(jax.random.key(0), env.spec.policy_dims)
    est, obs = env.reset(jax.random.PRNGKey(1), N)
    traj, _, _, last_v, _ = collect(params, env, est, obs,
                                    jax.random.PRNGKey(2), T, policy_fn)
    advs, rets = (gae_fused if fused else gae)(
        traj.rewards, traj.values, traj.dones, last_v, 0.99, 0.95)
    fields = [x.reshape((T * N,) + x.shape[2:])
              for x in (traj.obs, traj.actions, traj.log_probs, advs, rets)]
    idx = jax.random.permutation(jax.random.PRNGKey(3), T * N) \
        .reshape(M, T * N // M)

    tables, unpack = pack_rows(fields)
    width = sum(math.prod(x.shape[1:]) for x in fields)
    assert [t.shape for t in tables] == [(T * N, width)]
    got = unpack([t[idx] for t in tables])
    want = [jnp.take(x, idx, axis=0) for x in fields]
    for g, w in zip(got, want, strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_pack_rows_gives_a_field_of_another_dtype_its_own_table():
    from repro.rl.ppo import pack_rows
    R = 24
    ks = jax.random.split(jax.random.key(0), 3)
    fields = (jax.random.normal(ks[0], (R, 3, 5)),
              jax.random.randint(ks[1], (R, 2), 0, 9),
              jax.random.normal(ks[2], (R,)))
    tables, unpack = pack_rows(fields)
    assert [t.shape for t in tables] == [(R, 16), (R, 2)]
    idx = jax.random.permutation(jax.random.PRNGKey(1), R).reshape(2, R // 2)
    got = unpack([t[idx] for t in tables])
    for g, f in zip(got, fields, strict=True):
        assert g.dtype == f.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(f[idx]))


@pytest.mark.parametrize("fused", [False, True], ids=["gae", "gae_fused"])
@pytest.mark.parametrize("case", _PACK_CASES)
def test_packed_ppo_step_is_bit_identical_to_per_array_gathers(
        case, fused, monkeypatch):
    from repro.rl import ppo
    env, policy_fn = _packing_env(case)
    cfg = PPOConfig(num_steps=4, num_epochs=2, num_minibatches=4, lr=1e-3,
                    use_fused_kernels=fused)

    def first_steps(n=3):
        params, opt, est, obs = init_train(jax.random.key(0), env,
                                           env.spec.policy_dims, 16)
        step = make_train_step(env, cfg, policy_fn=policy_fn)
        state, out = (params, opt, est, obs, jax.random.PRNGKey(6)), []
        for _ in range(n):
            *state, metrics = step(*state)
            out.append((state[0], state[1], metrics))
        return out

    packed = first_steps()
    monkeypatch.setattr(ppo, "pack_rows", _per_array_pack)
    per_array = first_steps()
    for g, w in zip(jax.tree.leaves(packed), jax.tree.leaves(per_array),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("fused", [False, True], ids=["gae", "gae_fused"])
def test_lowered_ppo_step_gathers_the_samples_once_per_epoch(fused):
    """The epoch scan row-gathers one packed table: exactly one gather in
    the program reads an operand of ``T * N`` rows."""
    import re
    T, N = 4, 8
    env = make_env("BallBalance")
    step = make_train_step(env, PPOConfig(num_steps=T, num_epochs=2,
                                          num_minibatches=2,
                                          use_fused_kernels=fused))
    p, o, es, obs = init_train(jax.random.key(0), env,
                               env.spec.policy_dims, N)
    text = step.lower(p, o, es, obs, jax.random.PRNGKey(1)).as_text()
    rows = re.findall(r'"stablehlo\.gather".*?: \(tensor<(\d+)x', text)
    assert rows.count(str(T * N)) == 1, rows
