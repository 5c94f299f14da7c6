import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.envs import SPECS, all_env_names, make_env


@pytest.mark.parametrize("name", all_env_names())
def test_obs_action_dims_match_table6(name):
    env = make_env(name)
    spec = env.spec
    # paper Table 6
    expected = {"Ant": (60, 8), "Anymal": (48, 12), "BallBalance": (24, 3),
                "FrankaCabinet": (23, 9), "Humanoid": (108, 21),
                "ShadowHand": (211, 20)}[name]
    assert (spec.obs_dim, spec.act_dim) == expected
    assert spec.policy_dims[0] == spec.obs_dim
    assert spec.policy_dims[-1] == spec.act_dim
    state, obs = env.reset(jax.random.PRNGKey(0), num_envs=8)
    assert obs.shape == (8, spec.obs_dim)
    a = jnp.zeros((8, spec.act_dim))
    state, obs, rew, done = env.step(state, a)
    assert obs.shape == (8, spec.obs_dim)
    assert rew.shape == (8,) and done.shape == (8,)
    assert bool(jnp.all(jnp.isfinite(obs))) and bool(jnp.all(jnp.isfinite(rew)))


def test_determinism():
    env = make_env("Ant")
    s1, o1 = env.reset(jax.random.PRNGKey(7), num_envs=4)
    s2, o2 = env.reset(jax.random.PRNGKey(7), num_envs=4)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    a = jnp.full((4, env.spec.act_dim), 0.3)
    _, o1n, r1, _ = env.step(s1, a)
    _, o2n, r2, _ = env.step(s2, a)
    np.testing.assert_array_equal(np.asarray(o1n), np.asarray(o2n))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_long_run_stability_and_autoreset():
    env = make_env("Humanoid")
    state, obs = env.reset(jax.random.PRNGKey(0), num_envs=16)
    key = jax.random.PRNGKey(1)
    dones = 0
    step = jax.jit(env.step)
    for i in range(200):
        key, k = jax.random.split(key)
        a = jax.random.uniform(k, (16, env.spec.act_dim), minval=-1,
                               maxval=1)
        state, obs, rew, done = step(state, a)
        dones += int(done.sum())
        assert bool(jnp.all(jnp.isfinite(obs))), f"step {i}"
    # t counter must never exceed the episode cap
    assert int(state.t.max()) <= env.spec.max_episode_len


def test_episode_cap_triggers_done():
    env = make_env("BallBalance")
    state, _ = env.reset(jax.random.PRNGKey(0), num_envs=2)
    state = state._replace(t=jnp.full((2,), env.spec.max_episode_len - 1,
                                      jnp.int32))
    a = jnp.zeros((2, env.spec.act_dim))
    state2, obs, rew, done = env.step(state, a)
    assert bool(done.all())
    # auto-reset: t back near zero
    assert int(state2.t.max()) <= 1


_SENSOR_SEEDS = {"Ant": 571461097, "Anymal": 1025405366,
                 "BallBalance": 414204116, "FrankaCabinet": 1481782954,
                 "Humanoid": 1470153286, "ShadowHand": 569249199}


@pytest.mark.parametrize("name", all_env_names())
def test_sensor_seed_is_pinned(name):
    """The sensor projection is seeded from a CRC-32 of the env name, a
    value fixed across processes and Python versions (``hash`` of a str
    is salted per process)."""
    from repro.envs.suite import sensor_seed
    assert sensor_seed(name) == _SENSOR_SEEDS[name]


def test_sensor_matrix_same_in_another_process():
    """A child with a different hash salt builds the identical sensor
    matrix, so the env — and the compiled vmap path, where the matrix is
    a constant — is the same in every process."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import numpy as np; from repro.envs import make_env; "
            "print(np.asarray(make_env('Humanoid').mega.sensor)"
            ".tobytes().hex())")
    env = dict(os.environ, PYTHONHASHSEED="12345", JAX_PLATFORMS="cpu",
               PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    here = np.asarray(make_env("Humanoid").mega.sensor).tobytes().hex()
    assert out.stdout.strip().splitlines()[-1] == here
