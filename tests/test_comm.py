"""The unified repro.comm subsystem: Algorithm-1 / cost-model strategy
selection, the Communicator object, single-switch average semantics, the
core.lgr removal guard, and the controller's reduction-strategy
re-plan loop.  (Numerical schedule parity on real multi-device grids
lives in tests/_multidev_checks.py — this file runs on one device.)"""
import importlib
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (Communicator, ReduceCostModel, STRATEGIES,
                        algorithm1, as_grad_sync, make_grad_sync, mpr_host,
                        select_reduction_strategy)
from repro.core.cost_model import (lgr_time_har, lgr_time_har3, lgr_time_mpr)
from repro.core.placement import (plan_async, plan_tcg_ex_training,
                                  plan_tcg_serving)


# ------------------------------------------------------------- selection ---
def test_algorithm1_verbatim_reexport():
    """placement.select_reduction_strategy is the comm one, and the
    Algorithm-1 shape logic is unchanged."""
    from repro.core import placement
    assert placement.select_reduction_strategy is select_reduction_strategy
    assert algorithm1([[0, 1, 2]]) == "mpr"
    assert algorithm1([[0], [1]]) == "mrr"
    assert algorithm1([[0, 1, 2], [3, 4]]) == "har"
    assert select_reduction_strategy([[0, 1], [2, 3]]) == "mrr"


def test_cost_model_candidates_and_feasibility():
    cm = ReduceCostModel(dev_per_inst=2)
    assert cm.candidates((2, 2, 2)) == ["mpr", "har", "har3"]   # t*d > g
    assert "mrr" in cm.candidates((4, 2, 1))                    # t <= g
    assert "har3" not in cm.candidates((4, 2, 1))               # no dev axis
    assert cm.candidates((1, 4, 1)) == ["mpr"]                  # single GPU
    with pytest.raises(ValueError, match="dev axis"):
        cm.time("har3", (2, 2, 1))


def test_cost_model_prefers_har3_on_fast_dev_links():
    """Table-2 ordering: with intra-instance links much faster than the
    instance-level domain, the 3-level schedule must win on a
    (gpu, inst, dev) grid — and the verbatim shape logic alone (which is
    dev-blind) would not have picked it."""
    M = 6e6
    B1, B2, B3 = 5e9, 200e9, 400e9
    assert lgr_time_har3(2, 2, 2, M, B1, B2, B3) \
        < lgr_time_har(2, 4, M, B1, B2) < lgr_time_mpr(2, 4, M, B1, B2)
    cm = ReduceCostModel(bw_intra=B1, bw_gpu=B2, bw_dev=B3,
                         bytes_per_round=M, dev_per_inst=2)
    mpl = [[0, 1], [2, 3]]
    assert select_reduction_strategy(mpl) == "mrr"              # shape only
    assert select_reduction_strategy(mpl, cm) == "har3"         # cost-aware
    # ragged layouts can't build an axis mesh: cost path stays in mpr/har
    assert select_reduction_strategy([[0, 1, 2], [3, 4]], cm) in ("mpr",
                                                                  "har")


def test_cost_model_degenerates_without_dev_axis():
    """On a plain (gpu, inst) grid the cost-scored choice agrees with the
    Table-2 best_lgr ordering (har beats mpr on fast interconnects)."""
    cm = ReduceCostModel(bytes_per_round=6e6, dev_per_inst=1)
    s = select_reduction_strategy([[0, 1, 2], [3, 4, 5]], cm)
    assert s == "har"                       # t=3 > g=2: mrr infeasible


# ---------------------------------------------------------- Communicator ---
def test_communicator_from_layouts():
    ex = plan_tcg_ex_training(2, 2, devices=list(range(4)),
                              devices_per_gpu=2)
    comm = ex.communicator()
    assert comm.strategy == ex.reduction_strategy() == "mrr"
    assert comm.grid == (2, 2)
    assert plan_tcg_serving(2, 2, devices=list(range(8)),
                            devices_per_gpu=4).communicator() is None


def test_communicator_multi_device_grid_carries_dev_axis():
    from repro.core.gmi import GMIManager
    from repro.core.placement import Layout
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)     # 2 devices each
        mgr.set_gpu(gid, gpu)
    layout = Layout("t", mgr, [], [0, 1, 2, 3])
    comm = layout.communicator()
    assert comm.grid == (2, 2, 2)
    assert comm.cost_model.dev_per_inst == 2
    assert comm.num_instances == 8
    # Algorithm 1 is dev-blind and would say "mrr" here, but mrr breaks
    # the one-ring-endpoint-per-chip rule on this grid (t*d=4 > g=2):
    # construction must land on a FEASIBLE strategy, never a state its
    # own switch() would reject
    assert comm.strategy in comm.candidates()
    # cost-aware construction picks the 3-level schedule here
    comm3 = layout.communicator(cost_model=ReduceCostModel())
    assert comm3.strategy == "har3"


def test_communicator_ragged_layout_restricts_candidates():
    """A ragged layout (unequal GMIs per GPU) has no axis mesh, so the
    communicator's candidate set must stay in mpr/har — switch() to mrr
    must refuse even when the flattened grid shape would allow it."""
    from repro.core.gmi import GMIManager
    from repro.core.placement import Layout
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    for gid, gpu, frac in [(0, 0, 0.25), (1, 1, 0.25), (2, 1, 0.25)]:
        mgr.add_gmi(gid, "trainer", frac)
        mgr.set_gpu(gid, gpu)
    layout = Layout("ragged", mgr, [], [0, 1, 2])
    comm = layout.communicator()
    assert comm.uniform is False
    assert set(comm.candidates()) == {"mpr", "har"}
    with pytest.raises(ValueError, match="not feasible"):
        comm.switch("mrr")


def test_communicator_rebind_tracks_new_layout():
    """AsyncRunner.replan rebinds the communicator to the re-planned
    layout: grid/dev axis refresh, stale measurements clear, and an
    infeasible current strategy is coerced to a feasible one."""
    from repro.core.gmi import GMIManager
    from repro.core.placement import Layout
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("har3", grid=(2, 2, 2), cost_model=cm)
    comm.observe(1.0)
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=2)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)     # 1 chip each now
        mgr.set_gpu(gid, gpu)
    layout = Layout("replanned", mgr, [], [0, 1, 2, 3])
    comm.rebind(layout)
    assert comm.grid == (2, 2)
    assert comm.cost_model.dev_per_inst == 1
    assert comm.measured("har3") is None     # stale table cleared
    assert comm.strategy in comm.candidates()   # har3 no longer feasible


def test_communicator_from_layout_rejects_mixed_device_counts():
    """Planning as if every GMI were single-chip would silently drop the
    dev axis — mirror instance_mesh and refuse mixed sizes loudly."""
    from repro.core.gmi import GMIManager
    from repro.core.placement import Layout
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=4)
    mgr.add_gmi(0, "trainer", 0.5)           # 2 devices
    mgr.set_gpu(0, 0)
    mgr.add_gmi(1, "trainer", 0.25)          # 1 device
    mgr.set_gpu(1, 1)
    layout = Layout("mixed", mgr, [], [0, 1])
    with pytest.raises(ValueError, match="mixed devices-per-GMI"):
        layout.communicator()


def test_communicator_duck_types_as_grad_sync():
    comm = Communicator("mrr", grid=(2, 2))
    fn = as_grad_sync(comm)
    g = {"w": jnp.ones((3,))}
    assert fn(g)["w"].shape == (3,)          # identity without a mesh
    assert as_grad_sync(None) is None
    plain = lambda x: x                                         # noqa: E731
    assert as_grad_sync(plain) is plain


def test_communicator_switch_is_pure_plumbing():
    comm = Communicator("mpr", grid=(2, 2, 2),
                        cost_model=ReduceCostModel(dev_per_inst=2))
    comm.observe(1.0, 6e6)
    comm.observe(0.1, 6e6, strategy="har3")
    assert comm.switch("har3") is comm
    assert comm.strategy == "har3"
    # stale measurements of non-active strategies are dropped (one bad
    # early sample must not outrank the model forever); the new active
    # strategy keeps its live record
    assert comm.measured("mpr") is None
    assert comm.measured("har3") == 0.1
    with pytest.raises(ValueError, match="not feasible"):
        comm.switch("mrr")                   # t*d > g on this grid
    with pytest.raises(ValueError, match="unknown"):
        comm.switch("ring-of-fire")


def test_make_drl_train_step_rejects_mesh_attached_communicator():
    """Same guard as AsyncRunner: the jitted per-instance PPO step cannot
    host an SPMD-only sync closure — fail clearly, not at trace time."""
    from repro.envs import make_env
    from repro.launch.steps import make_drl_train_step

    class _FakeMesh:
        axis_names = ("gpu", "inst")
    comm = Communicator("mrr", grid=(2, 2))
    comm.mesh = _FakeMesh()
    with pytest.raises(TypeError, match="SPMD-only"):
        make_drl_train_step(make_env("Ant"), communicator=comm)


def test_propose_switch_measured_hysteresis():
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("mpr", grid=(2, 2, 2), cost_model=cm)
    assert comm.propose_switch() is None     # nothing measured yet
    for _ in range(3):                       # persistent: mpr is slow
        comm.observe(1.0)
    assert comm.propose_switch(1.05) == "har3"
    # measured evidence on a candidate beats the model: once har3 has
    # actually measured WORSE than mpr (steady state, not a lone compile
    # round) it drops out, and the proposal falls back to the next-best
    # (model-scaled) candidate
    comm.observe(2.0, strategy="har3")
    comm.observe(2.0, strategy="har3")
    assert comm.propose_switch(1.05) == "har"
    # marginal disagreement stays put (hysteresis)
    best = Communicator("har3", grid=(2, 2, 2), cost_model=cm)
    for _ in range(3):
        best.observe(1.0)
    assert best.propose_switch(1.05) is None


def test_observe_discards_compile_round_first_sample():
    """Satellite bugfix: the per-strategy EMA used to be SEEDED with the
    first observation — on any jitted path the compile round, exactly
    the stale one-off sample switch() warns about.  A synthetic 100x
    slower first sample must vanish from the EMA at the second."""
    comm = Communicator("mpr", grid=(2, 2))
    comm.observe(100.0)                      # compile round: 100x slower
    assert comm.measured("mpr") == 100.0     # provisional until steady
    comm.observe(1.0)
    assert comm.measured("mpr") == 1.0       # reseeded, poison discarded
    comm.observe(1.0)
    assert comm.measured("mpr") == pytest.approx(1.0)
    # had the 100x sample stayed in a 0.5-EMA it would still be ~25x off
    # here; the steady-state table must not remember it at all
    sec, nbytes, count = comm.measurements()["mpr"]
    assert sec == pytest.approx(1.0) and count == 3


def test_propose_switch_needs_min_observation_count():
    """Satellite bugfix: propose_switch used to fire off a SINGLE
    observation of the current strategy — one GC pause could trigger a
    drain-free switch.  1-2 noisy samples never switch; persistent
    evidence still does."""
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("mpr", grid=(2, 2, 2), cost_model=cm)
    comm.observe(50.0)                       # one GC-pause-sized outlier
    assert comm.propose_switch(1.05) is None
    comm.observe(1.0)
    assert comm.propose_switch(1.05) is None  # still below min_count
    comm.observe(1.0)
    assert comm.propose_switch(1.05) == "har3"   # persistent evidence
    # the knob is honest: a higher floor keeps refusing
    assert comm.propose_switch(1.05, min_count=10) is None


# ------------------------------------------------------ average semantics --
def test_mpr_host_single_average_switch():
    gs = [{"w": jnp.full((4,), float(i))} for i in range(1, 5)]
    mean = mpr_host(gs)
    total = mpr_host(gs, average=False)
    np.testing.assert_allclose(mean["w"], np.full(4, 2.5))
    np.testing.assert_allclose(total["w"], np.full(4, 10.0))


def test_make_grad_sync_rejects_bad_inputs():
    with pytest.raises(ValueError, match="unknown"):
        make_grad_sync("nccl", ("gpu", "inst"))
    with pytest.raises(ValueError, match="at least"):
        make_grad_sync("mrr", ("gpu",))


# -------------------------------------------------------------- lgr shim ---
def test_core_lgr_shim_removed():
    # the PR 3 deprecation shim is gone for good: importing it must fail
    # outright rather than silently resurrecting the old surface
    sys.modules.pop("repro.core.lgr", None)
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.core.lgr")
    import repro.core
    with pytest.raises(AttributeError):
        repro.core.lgr  # no lazy __getattr__ hook left either


# ------------------------------------------------- bandwidth calibration ---
def _planted_truth():
    """This-host-like ground truth: the host-staged instance-level domain
    is FAST and the cross-GPU interconnect slow — the regime where the
    static defaults mis-rank strategies (ROADMAP: mpr wins here while
    the Table-2 defaults say otherwise)."""
    return ReduceCostModel(bw_intra=400e9, bw_gpu=5e9, bw_dev=50e9,
                           bytes_per_round=6e6, dev_per_inst=2)


def _feed(comm_or_cal, truth, grid, strategies, n=3, noise=0.0, seed=0):
    rng = np.random.default_rng(seed)
    for s in strategies:
        for _ in range(n):
            sec = truth.time(s, grid) * (1 + noise * rng.standard_normal())
            if isinstance(comm_or_cal, Communicator):
                comm_or_cal.observe(sec, 6e6, strategy=s)
            else:
                comm_or_cal.add(s, grid, sec, 6e6)


def _feed_transfers(comm, truth, n=2, nbytes=1e6):
    """Channel-transfer telemetry consistent with the planted B1 — the
    redundant evidence the fit demands before trusting its residual."""
    for _ in range(n):
        comm.observe_transfer(nbytes / truth.bw_intra, nbytes)


def test_calibrator_recovers_planted_bandwidths_2x2():
    from repro.comm import BandwidthCalibrator
    truth = _planted_truth()
    cal = BandwidthCalibrator(base=ReduceCostModel(bytes_per_round=6e6))
    _feed(cal, truth, (2, 2), ("mpr", "mrr", "har"))
    fit = cal.fit()
    assert fit is not None
    assert fit.bw_intra == pytest.approx(400e9, rel=0.10)
    assert fit.bw_gpu == pytest.approx(5e9, rel=0.10)
    # no dev axis anywhere in the evidence: B3 stays the base default
    assert fit.solved == ("B1", "B2")
    assert fit.bw_dev == cal.base.bw_dev


def test_calibrator_recovers_planted_bandwidths_2x2x2_all_strategies():
    """Acceptance: all four strategy forms, both grids, noisy timings —
    every planted bandwidth recovered within 10%."""
    from repro.comm import BandwidthCalibrator
    truth = _planted_truth()
    cal = BandwidthCalibrator(base=ReduceCostModel(bytes_per_round=6e6,
                                                   dev_per_inst=2))
    _feed(cal, truth, (2, 2), ("mpr", "mrr", "har"), noise=0.02)
    _feed(cal, truth, (2, 2, 2), ("mpr", "har", "har3"), noise=0.02,
          seed=1)
    fit = cal.fit()
    assert fit is not None
    assert fit.solved == ("B1", "B2", "B3")
    assert sorted(fit.strategies) == ["har", "har3", "mpr", "mrr"]
    assert fit.bw_intra == pytest.approx(400e9, rel=0.10)
    assert fit.bw_gpu == pytest.approx(5e9, rel=0.10)
    assert fit.bw_dev == pytest.approx(50e9, rel=0.10)


def test_calibrator_refuses_ill_conditioned_input():
    """One strategy observed — however many samples — cannot separate
    the axes it mixes: no model is emitted.  Neither is one for an
    exactly-determined system (zero residual by construction, so noise
    would be accepted blindly)."""
    from repro.comm import BandwidthCalibrator
    cal = BandwidthCalibrator()
    for _ in range(20):
        cal.add("har", (2, 2), 1e-3, 6e6)
    assert cal.fit() is None
    assert cal.calibrated_model() is None
    # below the per-cell sample floor nothing fits either
    thin = BandwidthCalibrator(min_count=3)
    thin.add("mpr", (2, 2), 1e-3, 6e6)
    thin.add("har", (2, 2), 1e-3, 6e6)
    assert thin.fit() is None
    # two cells over two axes is square: refused until a redundant
    # equation lets the residual gate actually see disagreement
    truth = _planted_truth()
    square = BandwidthCalibrator(base=ReduceCostModel(bytes_per_round=6e6))
    _feed(square, truth, (2, 2), ("mpr", "har"))
    assert square.fit() is None
    _feed(square, truth, (4, 2), ("har",))
    assert square.fit() is not None


def test_calibrator_residual_gate_rejects_inconsistent_evidence():
    """A redundant system whose equations disagree wildly (timings that
    no bandwidth assignment explains) must not emit a model."""
    from repro.comm import BandwidthCalibrator
    truth = _planted_truth()
    cal = BandwidthCalibrator(base=ReduceCostModel(bytes_per_round=6e6))
    _feed(cal, truth, (2, 2), ("mpr", "mrr", "har"))
    assert cal.fit() is not None
    # an mpr cell on another grid claiming 50x the consistent B1 rate
    for _ in range(3):
        cal.add("mpr", (4, 2), truth.time("mpr", (4, 2)) * 50.0, 6e6)
    assert cal.fit() is None                 # residual gate refuses


def test_calibrator_transfer_timings_condition_b1():
    """Channel-transfer timings are B1 evidence: mrr alone only sees B2,
    but together with the pipeline's transfer stream the fit conditions."""
    from repro.comm import BandwidthCalibrator
    truth = _planted_truth()
    cal = BandwidthCalibrator(base=ReduceCostModel(bytes_per_round=6e6))
    _feed(cal, truth, (2, 2), ("mrr",))
    _feed(cal, truth, (4, 2), ("mrr",))      # second cell, still B2-only
    assert cal.fit() is None                 # ill-conditioned
    for _ in range(3):
        cal.add_transfer(1e6 / 400e9, 1e6)   # 1 MB over the planted B1
    fit = cal.fit()
    assert fit is not None
    assert fit.bw_intra == pytest.approx(400e9, rel=0.10)
    assert fit.bw_gpu == pytest.approx(5e9, rel=0.10)


def test_calibrated_communicator_flips_selection():
    """Acceptance: a Communicator under the calibrated model selects the
    planted-best strategy on a grid where the static defaults pick
    wrongly — and estimate()/candidates() re-score transparently."""
    base = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    truth = _planted_truth()
    comm = Communicator("har3", grid=(2, 2, 2), cost_model=base,
                        calibrate=True)
    assert base.best((2, 2, 2)) == "har3"        # static defaults: wrong
    assert truth.best((2, 2, 2)) == "mpr"        # planted reality
    assert comm.calibrated_cost_model() is None  # nothing measured yet
    _feed(comm, truth, (2, 2, 2), comm.candidates(), noise=0.02)
    _feed_transfers(comm, truth)                 # redundant B1 evidence
    cm = comm.calibrated_cost_model()
    assert cm is not None and comm.calibrated
    assert cm.best((2, 2, 2)) == "mpr"
    assert comm.effective_cost_model is cm
    # estimate() now answers with measured-bandwidth predictions
    assert comm.estimate("mpr") == pytest.approx(
        truth.time("mpr", (2, 2, 2)), rel=0.10)
    # and the live proposal agrees past the hysteresis
    assert comm.propose_switch(1.05) == "mpr"


def test_calibrated_flip_respects_hysteresis():
    """A calibrated model that disagrees with the default flips selection
    ONLY past the 1.05x hysteresis."""
    def comm_with(bw_gpu):
        truth = ReduceCostModel(bw_intra=100e9, bw_gpu=bw_gpu,
                                bytes_per_round=6e6)
        comm = Communicator("har", grid=(2, 2), calibrate=True,
                            cost_model=ReduceCostModel(bytes_per_round=6e6))
        _feed(comm, truth, (2, 2), ("har", "mrr"))
        _feed_transfers(comm, truth)
        assert comm.calibrated
        return comm
    # t_har/t_mpr = (x1+x2)/(1.5*x1): B2 = B1/0.545 -> ratio ~1.03 < 1.05
    assert comm_with(100e9 / 0.545).propose_switch(1.05) is None
    # B2 = B1/1.25 -> ratio 1.5 > 1.05: the flip to mpr goes through
    assert comm_with(100e9 / 1.25).propose_switch(1.05) == "mpr"


def test_communicator_propose_probe_conditions_the_fit():
    """While the fit lacks evidence the communicator names feasible
    strategies to measure; a probe in progress is left alone until its
    cell fills (one visit per candidate, never bounced and revisited);
    once every candidate is measured it stops."""
    truth = _planted_truth()
    base = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("mpr", grid=(2, 2, 2), cost_model=base,
                        calibrate=True)
    assert comm.propose_probe() is None      # measure where we stand first
    _feed(comm, truth, (2, 2, 2), ("mpr",))
    probe = comm.propose_probe()
    assert probe in ("har", "har3")
    comm.switch(probe)                       # what the controller applies
    comm.observe(truth.time(probe, comm.grid))   # compile round: discarded
    comm.observe(truth.time(probe, comm.grid))   # first steady sample
    assert comm.propose_probe() is None      # probe still collecting: stay
    comm.observe(truth.time(probe, comm.grid))   # cell reaches min_count
    probe2 = comm.propose_probe()
    assert probe2 not in (None, probe, "mpr")
    _feed(comm, truth, (2, 2, 2), (probe2,))
    assert comm.propose_probe() is None      # every candidate measured
    _feed_transfers(comm, truth)             # redundancy -> fit conditions
    assert comm.calibrated
    # without calibration there is nothing to condition: never probes
    plain = Communicator("mpr", grid=(2, 2, 2), cost_model=base)
    _feed(plain, truth, (2, 2, 2), ("mpr",))
    assert plain.propose_probe() is None


def test_communicator_rebind_keeps_calibration_observations():
    """Measured bandwidths are machine properties: a layout re-plan
    clears the per-strategy EMA table but NOT the calibration evidence
    (each observation carries its grid)."""
    from repro.core.gmi import GMIManager
    from repro.core.placement import Layout
    truth = _planted_truth()
    comm = Communicator("mpr", grid=(2, 2, 2),
                        cost_model=ReduceCostModel(dev_per_inst=2,
                                                   bytes_per_round=6e6),
                        calibrate=True)
    _feed(comm, truth, (2, 2, 2), ("mpr", "har", "har3"))
    _feed_transfers(comm, truth)
    assert comm.calibrated
    mgr = GMIManager(devices=list(range(8)), devices_per_gpu=2)
    for gid, gpu in [(0, 0), (1, 0), (2, 1), (3, 1)]:
        mgr.add_gmi(gid, "trainer", 0.5)     # 1 chip each now
        mgr.set_gpu(gid, gpu)
    comm.rebind(Layout("replanned", mgr, [], [0, 1, 2, 3]))
    assert comm.grid == (2, 2)
    assert comm.measured("mpr") is None      # EMA table cleared...
    assert comm.calibrated                   # ...calibration survives
    # and the calibrated bandwidths keep steering the NEW grid, where
    # the planted truth again favors the host-staged baseline
    assert comm.effective_cost_model.best((2, 2)) == \
        truth.best((2, 2))


def test_make_async_runner_calibrate_wires_the_loop():
    from repro.envs import make_env
    from repro.launch.steps import make_async_runner
    env = make_env("Ant")
    layout = plan_async(2, 1, 2, devices=list(range(4)), devices_per_gpu=2)
    runner = make_async_runner(env, layout, calibrate=True,
                               num_envs=8, num_steps=4)
    assert runner.communicator is not None
    assert runner.communicator.calibrator is not None
    # transfer telemetry flows: rounds produce pipeline transfer samples
    runner.round()
    assert runner.pipe.take_transfer_samples()
    runner.finish()


# --------------------------------------- controller reduction re-planning --
def _slow_mpr_comm():
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("mpr", grid=(2, 2, 2), cost_model=cm)
    for _ in range(3):
        comm.observe(1.0)                    # persistent: current is slow
    return comm


def test_controller_emits_reduction_strategy_replan():
    from repro.core.controller import ControllerConfig, OnlineGMIController
    comm = _slow_mpr_comm()
    c = OnlineGMIController(num_gpu=4, serving_gpus=2, gmi_per_gpu=2,
                            num_env=512,
                            cfg=ControllerConfig(epoch_rounds=1,
                                                 probe=False),
                            communicator=comm)
    from repro.core.controller import RoundSample
    d = c.record(RoundSample(samples=1000, dt=0.1, occupancy=0.5,
                             spills=0, mem_bytes=1e6))
    assert d is not None
    assert d.reduction_strategy == "har3"
    assert "reduce time" in d.reason
    # model state is not the controller's business: nothing else moved,
    # and the decision says so (runners switch in place, no rebuild)
    assert (d.num_env, d.gmi_per_gpu, d.serving_gpus) == (512, 2, 2)
    assert d.layout_changed is False


def test_controller_reduce_hysteresis_no_replan_when_best():
    from repro.core.controller import (ControllerConfig,
                                       OnlineGMIController, RoundSample)
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("har3", grid=(2, 2, 2), cost_model=cm)
    for _ in range(3):
        comm.observe(1.0)
    c = OnlineGMIController(num_gpu=4, serving_gpus=2, gmi_per_gpu=2,
                            num_env=512,
                            cfg=ControllerConfig(epoch_rounds=1,
                                                 probe=False),
                            communicator=comm)
    assert c.record(RoundSample(samples=1000, dt=0.1, occupancy=0.5,
                                spills=0, mem_bytes=1e6)) is None


def test_controller_schedules_calibration_probe():
    """Algorithm-2 explore for communication: while the calibration fit
    lacks evidence the controller emits an in-place probe of an
    unmeasured strategy (layout untouched)."""
    from repro.core.controller import (ControllerConfig,
                                       OnlineGMIController, RoundSample)
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("mpr", grid=(2, 2, 2), cost_model=cm,
                        calibrate=True)
    for _ in range(3):
        comm.observe(1.0)                    # current strategy measured
    c = OnlineGMIController(num_gpu=4, serving_gpus=2, gmi_per_gpu=2,
                            num_env=512,
                            cfg=ControllerConfig(epoch_rounds=1,
                                                 min_gain=1e9,  # no switch
                                                 probe=True,
                                                 num_env_sweep=(512,)),
                            communicator=comm)
    d = c.record(RoundSample(samples=1000, dt=0.1, occupancy=0.5,
                             spills=0, mem_bytes=1e6))
    assert d is not None
    assert d.reduction_strategy in ("har", "har3")
    assert d.layout_changed is False
    assert "probe reduction strategy" in d.reason


def test_controller_cites_calibrated_bandwidths():
    """A switch decision taken under a conditioned fit says so — the
    re-plan cites calibrated, not default, bandwidths."""
    from repro.core.controller import (ControllerConfig,
                                       OnlineGMIController, RoundSample)
    truth = _planted_truth()
    comm = Communicator("har3", grid=(2, 2, 2),
                        cost_model=ReduceCostModel(dev_per_inst=2,
                                                   bytes_per_round=6e6),
                        calibrate=True)
    _feed(comm, truth, (2, 2, 2), comm.candidates())
    _feed_transfers(comm, truth)
    c = OnlineGMIController(num_gpu=4, serving_gpus=2, gmi_per_gpu=2,
                            num_env=512,
                            cfg=ControllerConfig(epoch_rounds=1,
                                                 probe=False),
                            communicator=comm)
    d = c.record(RoundSample(samples=1000, dt=0.1, occupancy=0.5,
                             spills=0, mem_bytes=1e6))
    assert d is not None and d.reduction_strategy == "mpr"
    assert "calibrated Table-2 bandwidths" in d.reason


def test_controller_forwards_pipeline_transfer_timings():
    from repro.core.controller import ControllerConfig, OnlineGMIController

    class _Pipe:
        spill_count = 0

        class stats:
            total_bytes = 0

        def take_occupancy_high_water(self):
            return 0.5

        def take_transfer_samples(self):
            return [(0.001, 1_000_000)]

    comm = Communicator("mpr", grid=(2, 2), calibrate=True)
    c = OnlineGMIController(num_gpu=4, serving_gpus=2, gmi_per_gpu=2,
                            num_env=512,
                            cfg=ControllerConfig(epoch_rounds=4,
                                                 probe=False),
                            communicator=comm)
    c.observe_pipeline(_Pipe(), samples=8, dt=0.1)
    assert comm.calibrator.transfer_count == 1


def test_controller_round_sample_reduce_s_feeds_communicator():
    from repro.core.controller import (ControllerConfig,
                                       OnlineGMIController, RoundSample)
    cm = ReduceCostModel(dev_per_inst=2, bytes_per_round=6e6)
    comm = Communicator("mpr", grid=(2, 2, 2), cost_model=cm)
    c = OnlineGMIController(num_gpu=4, serving_gpus=2, gmi_per_gpu=2,
                            num_env=512,
                            cfg=ControllerConfig(epoch_rounds=2,
                                                 probe=False),
                            communicator=comm)
    c.record(RoundSample(samples=1000, dt=0.1, occupancy=0.5, spills=0,
                         mem_bytes=1e6, reduce_s=0.5))
    assert comm.measured("mpr") == 0.5       # flowed through record()


def test_async_runner_replan_switches_strategy_keeps_model_state():
    """Acceptance: a reduction-strategy re-plan applies through
    AsyncRunner.replan as communication plumbing only — parameters,
    optimizer state, and version survive bit-identically."""
    from repro.core.controller import Decision
    from repro.envs import make_env
    from repro.launch.steps import make_async_runner
    env = make_env("Ant")
    # devices_per_gpu=4 with 2 GMIs/GPU -> 2 chips per GMI: the trainer
    # grid keeps its dev axis across the re-plan, so har3 stays feasible
    layout = plan_async(4, 2, 2, devices=list(range(16)),
                        devices_per_gpu=4)
    comm = _slow_mpr_comm()
    runner = make_async_runner(env, layout, overlap=True,
                               communicator=comm, num_envs=8, num_steps=4)
    runner.round()
    runner.round()
    runner.finish()                          # drain: nothing left in flight
    params_before = jax.tree.map(np.asarray, runner.params)
    opt_mu_before = jax.tree.map(np.asarray, runner.opt_state.mu)
    version_before = int(runner.version)
    runner.layout_builder = lambda d: plan_async(
        4, d.serving_gpus, d.gmi_per_gpu, devices=list(range(16)),
        devices_per_gpu=4)
    runner.replan(Decision(num_env=8, gmi_per_gpu=2, serving_gpus=2,
                           reason="test", reduction_strategy="har3"))
    assert runner.communicator.strategy == "har3"
    # the strategy switch is communication plumbing only: params,
    # optimizer state, and version survive bit-identically
    assert int(runner.version) == version_before
    for a, b in zip(jax.tree.leaves(params_before),
                    jax.tree.leaves(jax.tree.map(np.asarray,
                                                 runner.params))):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(opt_mu_before),
                    jax.tree.leaves(jax.tree.map(np.asarray,
                                                 runner.opt_state.mu))):
        np.testing.assert_array_equal(a, b)
    # rounds keep working under the switched schedule
    ls, stale = runner.round()
    ls2, _ = runner.round()
    assert all(np.isfinite(ls + ls2))
    runner.finish()
    assert runner.trained_samples == runner.predictions


def test_async_runner_communicator_contract():
    """The runner never times the mesh-less identity closure into
    the switch hysteresis (measured reduce seconds only enter through
    RoundSample.reduce_s / direct observe), and rejects mesh-attached
    communicators outright — their sync closure is SPMD-only."""
    from repro.envs import make_env
    from repro.launch.steps import make_async_runner
    env = make_env("Ant")
    layout = plan_async(2, 1, 2, devices=list(range(4)), devices_per_gpu=2)
    comm = Communicator("mrr", grid=(2, 2))
    runner = make_async_runner(env, layout, communicator=comm,
                               num_envs=8, num_steps=4)
    runner.round()
    runner.round()
    assert comm.measured("mrr") is None      # no-op timings never recorded

    class _FakeMesh:
        axis_names = ("gpu", "inst")
    meshy = Communicator("mrr", grid=(2, 2))
    meshy.mesh = _FakeMesh()
    with pytest.raises(TypeError, match="SPMD-only"):
        make_async_runner(env, layout, communicator=meshy,
                          num_envs=8, num_steps=4)


def test_strategy_only_decision_switches_in_place_without_replan():
    """A decision that moves ONLY the reduction strategy must not pay the
    drain-and-rebuild re-plan: the runner switches the communicator in
    place mid-round-loop."""
    from repro.core.controller import ControllerConfig
    from repro.envs import make_env
    from repro.launch.steps import make_async_runner
    env = make_env("Ant")
    layout = plan_async(4, 2, 2, devices=list(range(8)), devices_per_gpu=2)
    comm = _slow_mpr_comm()
    runner = make_async_runner(
        env, layout, overlap=True, online_controller=True,
        communicator=comm,
        controller_cfg=ControllerConfig(epoch_rounds=1, probe=False,
                                        occ_low=0.0),
        num_envs=8, num_steps=4)
    pipe_before = runner.pipe
    runner.round()                           # overlap: trains one behind
    runner.round()                           # epoch boundary: decision
    assert runner.controller.decisions, "expected a decision"
    d = runner.controller.decisions[0]
    assert d.reduction_strategy == "har3" and not d.layout_changed
    assert runner.communicator.strategy == "har3"
    assert runner.pipe is pipe_before        # no rebuild
    assert runner.replans == 0
    runner.finish()
    assert runner.trained_samples == runner.predictions
