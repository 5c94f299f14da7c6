"""``correct`` comes out false when the timed path is broken underneath,
and for the control.  The harness runs as on the chip (its look for a
chip skipped) at a tiny size on the CPU, with one fault planted in the
program: a step that returns its state unchanged, or half of every batch
left out of the loss with the mean taken over the rest.  The control is
the reference computed in bfloat16 in the program's place."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run                                               # noqa: E402
from tests_support import tiny                           # noqa: E402


def frozen_ppo(mp):
    import repro.rl.ppo as ppo
    orig = ppo.train_iteration

    def frozen(params, opt_state, *a, **k):
        return (params, opt_state, *orig(params, opt_state, *a, **k)[2:])
    mp.setattr(ppo, "train_iteration", frozen)


def half_ppo(mp):
    import repro.rl.ppo as ppo
    orig = ppo.ppo_loss

    def half(params, batch, *a, **k):
        n = batch[0].shape[0] // 2
        return orig(params, tuple(x[:n] for x in batch), *a, **k)
    mp.setattr(ppo, "ppo_loss", half)


def frozen_a3c(mp):
    import repro.rl.a3c as a3c
    orig = a3c.trainer_update

    def frozen(params, opt_state, exp, **k):
        return params, opt_state, orig(params, opt_state, exp, **k)[2]
    mp.setattr(a3c, "trainer_update", frozen)


def half_a3c(mp):
    import repro.rl.a3c as a3c
    orig = a3c.a3c_loss

    def half(params, exp, *a, **k):
        n = exp.rewards.shape[1] // 2
        return orig(params, exp._replace(
            obs=exp.obs[:, :n], actions=exp.actions[:, :n],
            rewards=exp.rewards[:, :n], dones=exp.dones[:, :n],
            bootstrap=exp.bootstrap[:n]), *a, **k)
    mp.setattr(a3c, "a3c_loss", half)


FAULTS = [("sh_ppo_16k", frozen_ppo), ("sh_ppo_16k", half_ppo),
          ("ant_a3c_mega_2x16k", frozen_a3c),
          ("ant_a3c_mega_2x16k", half_a3c)]


@pytest.mark.parametrize("name,plant", FAULTS,
                         ids=[f"{n}-{p.__name__}" for n, p in FAULTS])
def test_fault_is_not_correct(name, plant, monkeypatch):
    import jax
    plant(monkeypatch)
    res = run.run_cell(tiny(name), 2 ** 31 + 7, 0.5, False,
                       jax.devices()[:1])
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("name", ["sh_ppo_16k", "ant_a3c_mega_2x16k"])
def test_control_is_not_correct(name):
    import importlib
    from benchlib import training_check as tc
    cell = tiny(name)
    mod = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    seed = 2 ** 31 + 11
    ref = mod.reference_first_steps(cell.config, cell.traffic, seed)
    ctrl = mod.reference_first_steps(cell.config, cell.traffic, seed,
                                     dtype="bfloat16")
    numbers = tc.numbers(ctrl, ref)
    numbers.update({k: 0.0 for k in cell.limits["numbers"]
                    if k not in numbers})
    assert not run.judge(numbers, cell.limits["numbers"])[0]
