"""Launch-time builders: the launcher, not the algorithm module, decides
which hot path a step compiles to and how the experience pipeline is laid
out over GMIs.  The benchmark (``bench/``), ``chip_smoke.py`` and the
CLIs call:

* :func:`make_drl_train_step` — the jitted sync-PPO iteration;
* :func:`make_lm_policy_train_step` — the jitted GRPO step of a token
  policy on the latent-attention MoE stack;
* :func:`make_communicator`, :func:`make_experience_pipeline` — the
  layout's ``repro.comm.Communicator`` and its channel pipeline;
* :func:`make_online_controller`, :func:`make_async_runner` — the
  Algorithm-2 controller and the async A3C runner over that pipeline;
* :func:`make_disagg_front`, :func:`make_fleet_supervisor` — the
  disaggregated serving front and the fault-tolerant fleet.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax

from repro.configs.base import ModelConfig


def make_communicator(layout, cost_model=None, *, average: bool = True,
                      with_mesh: bool = False, calibrate: bool = False):
    """The layout's ``repro.comm.Communicator``: instance grid off the
    trainer MPL (incl. the trailing ``dev`` axis for multi-device GMIs),
    strategy from Algorithm 1 — or Table-2 cost-scored when a
    ``ReduceCostModel`` is supplied.  ``None`` for serving-only layouts.
    ``calibrate=True`` attaches a ``BandwidthCalibrator`` so measured
    reduce/transfer timings replace the model's static per-axis
    bandwidth defaults once the Table-2 inversion is conditioned."""
    comm = layout.communicator(cost_model, average=average,
                               with_mesh=with_mesh)
    if comm is not None and calibrate:
        comm.enable_calibration()
    return comm


def make_drl_train_step(env, ppo_cfg=None, communicator=None):
    """Jitted sync-PPO iteration with the fused Pallas hot path on by
    default: the gae_scan kernel (GAE + advantage normalization in one
    VMEM pass).  An explicit ``ppo_cfg`` keeps its own
    ``use_fused_kernels``.  Gradient sync comes from ``communicator`` (a
    ``repro.comm.Communicator``) when given."""
    from repro.rl.ppo import PPOConfig, make_train_step
    cfg = ppo_cfg if ppo_cfg is not None \
        else PPOConfig(use_fused_kernels=True)
    if communicator is not None and communicator.mesh is not None:
        # same guard AsyncRunner applies: this builder jits an eager
        # per-instance step, and a mesh-attached Communicator's sync
        # closure is SPMD-only — failing here beats an unbound-axis-name
        # error deep inside the first traced step
        raise TypeError(
            "make_drl_train_step builds a plain-jit per-instance step; a "
            "mesh-attached Communicator's sync closure is SPMD-only (use "
            "Communicator.allreduce in a shard_map launcher, or a "
            "mesh-less Communicator here)")
    return make_train_step(env, cfg, communicator), cfg


def make_lm_policy_train_step(model_cfg: ModelConfig, grpo_cfg=None,
                              first_expert: int = 0):
    """Jitted GRPO step for a token policy on the latent-attention MoE
    stack (``rl/grpo.py::train_step``): ``(params, opt_state, batch) ->
    (params, opt_state, metrics)``.  The parameters and Adam state are
    donated (a step holds a second copy of neither); the MoE layers hold
    experts ``[first_expert, first_expert + model_cfg.experts_held)``."""
    from repro.rl import grpo
    cfg = grpo_cfg if grpo_cfg is not None else grpo.GRPOConfig()

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        return grpo.train_step(params, opt_state, batch, cfg, model_cfg,
                               first_expert)

    return step


def make_experience_pipeline(layout, batch_mode: str = "stack",
                             batch_envs: Optional[int] = None,
                             overlap: bool = False):
    """Device-resident MCC pipeline wired from an async placement layout:
    ring slots sized to the layout's serving GMIs and the per-GMI GPU map
    passed through so the Migrator can direct-forward same-GPU groups.
    ``overlap=True`` double-buffers the rings so a flush is a buffer swap
    — serving GMIs keep packing while trainer GMIs consume the previous
    flush (paper §4.1 serve/train overlap)."""
    from repro.core.channels import MultiChannelPipeline
    gmi_gpu = {g.gmi_id: g.gpu_id for g in layout.manager.gmis.values()}
    return MultiChannelPipeline(layout.serving_gmis, layout.trainer_gmis,
                                gmi_gpu=gmi_gpu, batch_mode=batch_mode,
                                batch_envs=batch_envs, overlap=overlap)


def make_online_controller(layout, num_env: int, controller_cfg=None,
                           communicator=None):
    """Online Algorithm-2 controller seeded from an async placement
    layout: the live (serving_gpus, gmi_per_gpu, num_env) become the
    first measured configuration; the controller then re-plans the
    layout between training epochs from measured throughput and ring
    occupancy (see ``repro.core.controller``).  With a ``communicator``
    attached, measured reduce times can additionally re-plan the LGR
    strategy."""
    from repro.core.controller import OnlineGMIController
    gmis = layout.manager.gmis.values()
    serving_gpus = {g.gpu_id for g in gmis if g.role == "serving"}
    all_gpus = {g.gpu_id for g in gmis}
    per_gpu: Dict[int, int] = {}
    for g in gmis:
        per_gpu[g.gpu_id] = per_gpu.get(g.gpu_id, 0) + 1
    return OnlineGMIController(
        num_gpu=len(all_gpus), serving_gpus=max(len(serving_gpus), 1),
        gmi_per_gpu=max(per_gpu.values()), num_env=num_env,
        cfg=controller_cfg, communicator=communicator)


def make_async_runner(env, layout, overlap: bool = False,
                      online_controller: bool = False,
                      controller_cfg=None, communicator=None,
                      calibrate: bool = False, megakernel: bool = False,
                      **kwargs):
    """Async A3C driver over ``make_experience_pipeline(layout)``.

    ``megakernel=True`` flips the env onto the fused megakernel step
    path (``VectorEnv.with_megakernel``); on blocking (non-overlap)
    pipelines the runner then produces experience straight into the
    channel-ring slots via ``rl.rollout.collect_ring`` — the zero-copy
    producer path.
    ``overlap=True`` runs the double-buffered serve-while-train pipeline;
    ``online_controller=True`` attaches an Algorithm-2 controller that
    re-plans the GMI layout between training epochs from live stats.
    ``communicator=True`` builds the layout's Communicator (gradient
    reduction through ``repro.comm``, timed per round); an explicit
    Communicator instance is used as-is.  ``calibrate=True`` enables
    measured-bandwidth calibration on the communicator (building one
    from the layout if none was asked for): live reduce and
    channel-transfer timings then feed the Table-2 inversion, and the
    controller's strategy decisions re-score against the fitted
    bandwidths instead of the static defaults."""
    from repro.rl.a3c import AsyncRunner
    if megakernel:
        env = env.with_megakernel(True)
    if communicator is True or (calibrate and communicator is None):
        communicator = make_communicator(layout, calibrate=calibrate)
    elif calibrate and communicator is not None:
        communicator.enable_calibration()
    controller = None
    layout_builder = None
    if online_controller:
        controller = make_online_controller(
            layout, num_env=kwargs.get("num_envs", 64),
            controller_cfg=controller_cfg, communicator=communicator)

        def layout_builder(decision):
            # re-plan inside the SAME device universe the seed layout
            # was built over (may be synthetic ids in tests/benchmarks)
            from repro.core.placement import plan_async
            return plan_async(controller.num_gpu, decision.serving_gpus,
                              decision.gmi_per_gpu,
                              devices=layout.manager.devices,
                              devices_per_gpu=layout.manager.devices_per_gpu)

    return AsyncRunner(env, layout.serving_gmis, layout.trainer_gmis,
                       pipeline=make_experience_pipeline(layout,
                                                         overlap=overlap),
                       overlap=overlap, controller=controller,
                       layout_builder=layout_builder,
                       communicator=communicator or None, **kwargs)


def make_disagg_front(cfg, params, *, decode_engines: int = 2,
                      prefill_gmis: int = 1, max_slots: int = 4,
                      max_seq: int = 128,
                      window_override: Optional[int] = None,
                      communicator=None, latency_s: float = 100e-6,
                      min_gain: float = 1.05):
    """Disaggregated serving front (ROADMAP item 2): ``decode_engines``
    continuous-batching decode GMIs behind a ``RequestRouter`` plus
    ``prefill_gmis`` prefill specialists, joined by a ``CacheChannel``,
    with the per-request migrate-vs-local decision priced by a
    ``MigrationPlanner`` in Table-2 units (a ``communicator`` supplies
    calibrated bandwidths; the channel's own measured transfers sharpen
    them).  Both sides get factories, so ONE controller decision can
    re-split prefill/decode at runtime.  Pass the front as ``router=`` to
    :func:`make_async_runner` / :func:`make_fleet_supervisor` to put it
    under the single Algorithm-2 arbiter."""
    from repro.serve import (DisaggFront, MigrationPlanner, PrefillEngine,
                             RequestRouter, ServeEngine)

    def engine_factory(i, slots=max_slots):
        return ServeEngine(cfg, params, max_slots=slots, max_seq=max_seq,
                           window_override=window_override,
                           name=f"decode{i}")

    def prefill_factory(i):
        return PrefillEngine(cfg, params, max_seq=max_seq,
                             window_override=window_override,
                             name=f"prefill{i}")

    router = RequestRouter(engine_factory=engine_factory,
                           num_engines=decode_engines)
    planner = MigrationPlanner(communicator=communicator,
                               latency_s=latency_s, min_gain=min_gain)
    return DisaggFront(
        router, [prefill_factory(i) for i in range(max(prefill_gmis, 1))],
        planner=planner, prefill_factory=prefill_factory)


def make_fleet_supervisor(env, layout, *, plan=None, router=None,
                          ckpt_dir: Optional[str] = None,
                          ckpt_every: int = 0, probation: int = 2,
                          max_retries: int = 2, overlap: bool = False,
                          online_controller: bool = False, **kwargs):
    """Fault-tolerant elastic fleet over an async placement layout: a
    ``make_async_runner`` runner wrapped in a
    :class:`repro.fault.FleetSupervisor` — injection hooks armed at every
    seam, per-round failure classification, GPU quarantine with
    probation-gated re-admission, lossless re-plans onto the surviving
    pool, and (with ``ckpt_dir``/``ckpt_every``) periodic preemption-safe
    checkpoints through the atomic ``repro.checkpoint`` writer.  ``plan``
    is an optional :class:`repro.fault.FaultPlan` (deterministic fault
    schedule); ``router`` an optional serving front (``RequestRouter`` or
    ``DisaggFront``) to supervise too — it is ALSO handed to the runner,
    so the one controller instance arbitrating trainers and rollout
    actors folds the serving epochs into the same Algorithm-2 loop."""
    from repro.fault import FleetSupervisor
    runner = make_async_runner(env, layout, overlap=overlap,
                               online_controller=online_controller,
                               router=router, **kwargs)
    return FleetSupervisor(runner, layout, plan=plan, router=router,
                           ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                           probation=probation, max_retries=max_retries)
