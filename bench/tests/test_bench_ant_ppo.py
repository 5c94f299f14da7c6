"""``ant_ppo_16k`` (Ant under sync PPO on the vmap env path) at a tiny
size on the CPU: the cell runs through the harness and is correct, and
its limits refuse the planted faults of ``test_bench_faults.py`` and the
bfloat16 control."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run                                               # noqa: E402
from test_bench_faults import frozen_ppo, half_ppo      # noqa: E402
from tests_support import tiny                           # noqa: E402

CELL = "ant_ppo_16k"
SEED = 2 ** 31 + 5


def test_cell_runs_on_the_cpu_and_is_correct():
    import jax
    res = run.run_cell(tiny(CELL), SEED, 0.5, False, jax.devices()[:1])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["checks"]) == {"grad_gap", "delta_gap"}


@pytest.mark.parametrize("plant", [frozen_ppo, half_ppo],
                         ids=lambda p: p.__name__)
def test_fault_is_not_correct(plant, monkeypatch):
    import jax
    plant(monkeypatch)
    res = run.run_cell(tiny(CELL), SEED, 0.5, False, jax.devices()[:1])
    assert res["correct"] is False


def test_control_is_not_correct():
    from benchlib import training_check as tc
    from drivers import sync_ppo
    cell = tiny(CELL)
    ref = sync_ppo.reference_first_steps(cell.config, cell.traffic, SEED)
    ctrl = sync_ppo.reference_first_steps(cell.config, cell.traffic, SEED,
                                          dtype="bfloat16")
    assert not run.judge(tc.numbers(ctrl, ref), cell.limits["numbers"])[0]
