import numpy as np
import pytest

from repro.core.cost_model import (WorkloadProfile, best_lgr, lgr_time_har,
                                   lgr_time_mpr, lgr_time_mrr,
                                   serving_speedup_tcg_over_tdg,
                                   training_speedup_tcg_over_tdg)
from repro.core.gmi import GMIManager
from repro.core.placement import (plan_async, plan_tcg_ex_training,
                                  plan_tcg_serving, plan_tdg_serving,
                                  select_reduction_strategy)
from repro.core.selection import ProfilePoint, explore


def test_manager_registration_and_mapping():
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)
        mgr.set_gpu(gid, gpu)
    assert mgr.gmi_to_gpu_mapping("trainer") == [[0, 1], [2, 3]]
    assert mgr.gmis[0].num_devices == 2
    with pytest.raises(ValueError):
        mgr.add_gmi(0)
    # overcommit: a 5th half-GPU instance on gpu 0 must fail
    mgr.add_gmi(9, "trainer", 0.75)
    with pytest.raises(ValueError):
        mgr.set_gpu(9, 0)


def test_algorithm1_cases():
    # paper Algorithm 1, line-by-line behaviours
    assert select_reduction_strategy([[0, 1, 2]]) == "mpr"
    assert select_reduction_strategy([[0], [1]]) == "mrr"
    assert select_reduction_strategy([[0, 1], [2, 3], [4, 5]]) == "mrr"
    assert select_reduction_strategy([[0, 1, 2], [3, 4]]) == "har"
    assert select_reduction_strategy([[0, 1, 2], [3, 4, 5]]) == "har"


def test_layout_templates():
    tcg = plan_tcg_serving(2, 3, devices=list(range(12)), devices_per_gpu=6)
    assert len(tcg.serving_gmis) == 6
    tdg = plan_tdg_serving(2, 2, devices=list(range(20)),
                           devices_per_gpu=10)
    roles = {g.role for g in tdg.manager.gmis.values()}
    assert roles == {"simulator", "agent"}
    ex = plan_tcg_ex_training(2, 2, devices=list(range(8)),
                              devices_per_gpu=4)
    assert ex.reduction_strategy() == "mrr"       # t=2 == g=2
    ex2 = plan_tcg_ex_training(2, 3, devices=list(range(12)),
                               devices_per_gpu=6)
    assert ex2.reduction_strategy() == "har"      # t=3 > g=2
    asy = plan_async(4, 2, 2, devices=list(range(16)), devices_per_gpu=4)
    assert len(asy.serving_gmis) == 4 and len(asy.trainer_gmis) == 4


def test_lgr_cost_model_orderings():
    # Table 2: with NCCL bandwidth >> host bandwidth, HAR beats MPR, and the
    # HAR advantage grows with more instances per GPU
    M, B1, B2 = 1.5e6, 5e9, 200e9
    assert lgr_time_har(4, 4, M, B1, B2) < lgr_time_mpr(4, 4, M, B1, B2)
    assert best_lgr(2, 8, M, B1, B2) in ("har", "mpr")  # mrr infeasible t>g
    # absolute HAR saving exists at every scale (Table 2 with B2 >> B1)
    for g, t in [(2, 2), (4, 4), (8, 4)]:
        assert lgr_time_har(g, t, M, B1, B2) < lgr_time_mpr(g, t, M, B1, B2)
    # and HAR's cross-GPU stage rides the fast interconnect: doubling B2
    # shrinks HAR time but leaves MPR untouched
    assert lgr_time_har(4, 4, M, B1, 2 * B2) < lgr_time_har(4, 4, M, B1, B2)
    assert lgr_time_mpr(4, 4, M, B1, 2 * B2) == lgr_time_mpr(4, 4, M, B1, B2)


def test_paper_speedup_claims():
    s = serving_speedup_tcg_over_tdg()
    t = training_speedup_tcg_over_tdg()
    assert 2.0 < s < 3.2, f"serving speedup {s} out of the paper's ~2.5x band"
    assert 3.0 < t < 6.5, f"training speedup {t} out of the paper's ~5x band"


def test_algorithm2_finds_saturation_knee():
    """Synthetic profile: throughput saturates at num_env=2048; memory keeps
    growing — Algorithm 2 must not pick a post-knee config."""

    def profile(bench, gpg, ne):
        if gpg > 4:
            return ProfilePoint(False, 0.0, 0.0)     # too small to run
        top = 1000.0 * min(ne, 2048) ** 0.9 / gpg ** 0.2
        mem = ne * 1e6 / gpg
        return ProfilePoint(True, top, mem)

    trace = explore(profile, "AT", num_gpu=4, alpha=0.1)
    ne, gpg = trace.best_config
    assert ne <= 4096
    assert gpg <= 4
    assert trace.best_throughput > 0


def test_algorithm2_respects_runnability():
    def profile(bench, gpg, ne):
        return ProfilePoint(gpg == 1 and ne == 128, 10.0, 1.0)
    trace = explore(profile, "AT", num_gpu=1)
    assert trace.best_config == (128, 1)


def test_algorithm2_saturation_with_shrinking_memory():
    """Regression: when memory SHRINKS between sweep points while
    throughput still grows, Sat used to explode to ±1e9·r_top via the
    clamped denominator.  A throughput gain at no memory cost must never
    prune — the sweep has to reach the highest-throughput point."""
    mems = {128: 3e6, 256: 2e6, 512: 1e6}    # allocator slack: shrinking

    def profile(bench, gpg, ne):
        if gpg != 1 or ne not in mems:
            return ProfilePoint(False, 0.0, 0.0)
        return ProfilePoint(True, 100.0 * ne, mems[ne])

    trace = explore(profile, "AT", num_gpu=1, gmi_per_gpu_range=(1,),
                    num_env_sweep=(128, 256, 512))
    assert trace.best_config == (512, 1)     # swept to the end
    sats = [s for *_, s in trace.points]
    assert sats[1] == float("inf") and sats[2] == float("inf")


def test_algorithm2_flat_memory_no_gain_prunes_cleanly():
    """Flat memory + no throughput gain must stop the sweep with a
    well-defined Sat (-inf), not a ±1e9 artifact."""
    def profile(bench, gpg, ne):
        return ProfilePoint(gpg == 1, 100.0, 1e6)   # flat top, flat mem

    trace = explore(profile, "AT", num_gpu=1, gmi_per_gpu_range=(1,),
                    num_env_sweep=(128, 256, 512))
    assert trace.best_config == (128, 1)
    assert len(trace.points) == 2            # pruned right after point 2
    assert trace.points[-1][-1] == float("-inf")


def test_profiler_distinguishes_oom_from_genuine_bugs(monkeypatch):
    """Resource exhaustion -> 'not runnable'; a shape bug must raise, not
    be silently reported as an unrunnable config."""
    from repro.core.selection import is_resource_exhausted, make_ppo_profiler

    class FakeOOM(RuntimeError):
        pass

    assert is_resource_exhausted(MemoryError())
    assert is_resource_exhausted(FakeOOM("RESOURCE_EXHAUSTED: while trying"))
    assert is_resource_exhausted(FakeOOM("failed to allocate 2.1GiB"))
    assert not is_resource_exhausted(ValueError("shape mismatch (3,) (4,)"))

    def boom_oom(*a, **k):
        raise FakeOOM("RESOURCE_EXHAUSTED: out of memory allocating arena")

    monkeypatch.setattr("repro.rl.ppo.init_train", boom_oom)
    prof = make_ppo_profiler(iters=1, mem_budget_bytes=32e9)("BallBalance", 1, 128)
    assert not prof.runnable and prof.memory > 0

    def boom_bug(*a, **k):
        raise ValueError("operands could not be broadcast")

    monkeypatch.setattr("repro.rl.ppo.init_train", boom_bug)
    with pytest.raises(ValueError):
        make_ppo_profiler(iters=1, mem_budget_bytes=32e9)("BallBalance", 1, 128)


def test_instance_mesh_multi_device_keeps_all_chips():
    """Regression: multi-device GMIs used to contribute only
    device_ids[0] — a resized instance silently lost chips."""
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)     # 2 devices each
        mgr.set_gpu(gid, gpu)
    mesh = mgr.instance_mesh("trainer")
    assert mesh.axis_names == ("gpu", "inst", "dev")
    assert mesh.devices.shape == (2, 2, 2)
    assert sorted(mesh.devices.reshape(-1).tolist()) == list(range(8))


def test_multi_device_instance_mesh_is_reducible():
    """The (gpu, inst, dev) meshes instance_mesh builds for multi-device
    GMIs are first-class in repro.comm (the old 2-axis-only lgr_allreduce
    rejected them): every 3-axis schedule constructs, har3 refuses 2-axis
    grids, and >3-axis grids are still rejected loudly.  Numerical parity
    on real device grids lives in tests/_multidev_checks.py."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.comm import lgr_allreduce, make_grad_sync

    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)
        mgr.set_gpu(gid, gpu)
    mesh = mgr.instance_mesh("trainer")
    assert mesh.axis_names == ("gpu", "inst", "dev")
    for strat in ("mpr", "mrr", "har", "har3"):
        assert callable(make_grad_sync(strat, mesh.axis_names))
    with pytest.raises(ValueError, match="3-axis"):
        make_grad_sync("har3", ("gpu", "inst"))
    mesh4 = Mesh(np.arange(8).reshape(1, 2, 2, 2),
                 ("pod", "gpu", "inst", "dev"))
    with pytest.raises(ValueError, match="2-axis .* or 3-axis"):
        lgr_allreduce({"w": jnp.ones((1, 2, 2, 2, 3))}, mesh4, "mrr")


def test_instance_mesh_rejects_mixed_device_counts():
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    mgr.add_gmi(0, "trainer", 0.5)           # 2 devices
    mgr.set_gpu(0, 0)
    mgr.add_gmi(1, "trainer", 0.25)          # 1 device
    mgr.set_gpu(1, 1)
    with pytest.raises(ValueError, match="uniform"):
        mgr.instance_mesh("trainer")


def test_serving_only_layout_has_no_reduction_strategy():
    tcg = plan_tcg_serving(2, 2, devices=list(range(8)), devices_per_gpu=4)
    assert tcg.mpl == []
    assert tcg.reduction_strategy() is None
    with pytest.raises(ValueError, match="no trainer"):
        select_reduction_strategy([])
