"""CPU rehearsal of the harness: each driver at a tiny size (64 envs, 4
steps), the window arithmetic, the result line's schema, the data files
``BENCHMARK.json`` names, and the exit without a TPU."""
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import run                                               # noqa: E402
from tests_support import tiny                           # noqa: E402

CELLS = ["sh_ppo_16k", "ant_a3c_mega_2x16k"]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_benchmark_json_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["paths"] == ["bench"]
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["policy_dims"][0] == cfg["env"]["obs_dim"]
        assert cfg["policy_dims"][-1] == cfg["env"]["act_dim"]
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert (BENCH / "drivers" / f"{cell.traffic['driver']}.py").is_file()
        assert [m["name"] for m in cell.end_to_end] == \
            ["train_samples_per_s", "setup_s"]
    assert [m["name"] for m in run.load_cell(CELLS[0]).per_layer] == \
        ["policy_mfu", "gae_us_per_call", "compiles_in_window"]
    assert [m["name"] for m in run.load_cell(CELLS[1]).per_layer] == \
        ["policy_mfu", "nstep_roofline", "env_mega_ms_per_round",
         "compiles_in_window"]


def test_judge():
    assert run.judge({"a": 0.1}, {"a": 0.2})[0]
    assert not run.judge({"a": 0.3}, {"a": 0.2})[0]
    assert not run.judge({"a": float("nan")}, {"a": 0.2})[0]
    assert not run.judge({}, {"a": 0.2})[0]                      # no number
    ok, checks = run.judge({"a": 0.1, "b": 9.0}, {"a": 0.2})     # b: not
    assert ok and list(checks) == ["a"]                          # compared


@pytest.mark.parametrize("name", CELLS)
def test_driver_runs_the_cell_on_the_cpu(name):
    import jax
    cell = tiny(name)
    res = run.run_cell(cell, 2 ** 31 + 99, 1.0, False, jax.devices()[:1])
    assert list(res) == KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert {k: v["unit"] for k, v in res["metrics"].items()} == \
        {"train_samples_per_s": "samples/s", "setup_s": "s"}
    assert res["metrics"]["train_samples_per_s"]["value"] > 0
    assert set(res["checks"]) == set(cell.limits["numbers"])
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["device"]["count"] == 1
    json.dumps(res)


class StubDriver:
    """A driver whose unit takes 50 ms and trains 1000 samples."""
    UNIT = "iteration"

    def __init__(self, config, traffic, seed):
        from benchlib.training_check import FirstSteps
        self.units = self.attempted = self.failed = 0
        self.first = FirstSteps([1.0], {"w": np.ones(3)}, {"w": np.ones(3)})
        self.flops_per_sample, self.kernel_shapes = 1.0, {}
        self.calls = []

    def first_steps(self, n):
        pass

    def step(self):
        self.calls.append(time.perf_counter())
        time.sleep(0.05)
        self.units += 1
        self.attempted += 1

    def sync(self):
        self.calls.append(time.perf_counter())

    def trained_samples(self):
        return 1000 * self.units

    def end_window(self):
        return {}

    def release(self):
        pass

    def reference(self):
        return self.first


def test_window_arithmetic():
    import jax
    cell = tiny(CELLS[0])
    cell.limits = {"numbers": {"loss_gap": 0, "grad_gap": 0, "delta_gap": 0}}
    stub = []

    def make(*a):
        stub.append(StubDriver(*a))
        return stub[0]
    start = time.perf_counter()
    res = run.run_cell(cell, 1, 0.3, False, jax.devices()[:1],
                       process_start=start, driver=make)
    d = stub[0]
    window = d.calls[-1] - d.calls[0]
    rate = res["metrics"]["train_samples_per_s"]["value"]
    assert res["attempted"] == d.units >= 6
    assert rate == pytest.approx(1000 * d.units / window, rel=0.05)
    assert res["metrics"]["setup_s"]["value"] == \
        pytest.approx(d.calls[0] - start, abs=0.05)
    assert res["correct"] is True


def test_exits_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_peaks_are_keyed_by_device_kind():
    from benchlib.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
