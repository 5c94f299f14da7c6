"""Step builders: jitted train / prefill / serve steps with full sharding
annotations, plus ShapeDtypeStruct input factories for the dry-run.

LGR on the production mesh (DESIGN.md §2): the gradient-reduction schedule
is selected through the parameter LAYOUT, exactly the paper's insight that
the layout determines the schedule —

* ``--lgr mrr`` (flat)        : params replicated over (pod, data); autodiff
  gradient sync lowers to ONE flat all-reduce ring over every chip.
* ``--lgr har`` (hierarchical): params FSDP-sharded over ``data``,
  replicated over ``pod``; gradient sync lowers to reduce-scatter(data/ICI)
  → cross-pod all-reduce on 1/16-size shards → all-gather(data/ICI) — the
  paper's intra-reduce → leader-ring → broadcast, with each chip the leader
  of its shard slice.  Cross-pod (DCN) bytes drop 16x.

MPR (host-staged) is not expressible inside one HLO; it exists at the DRL
layer (``repro.comm.mpr_host``) where the paper applies it.  The DRL
builders below consume ``repro.comm.Communicator`` objects — the unified
communication subsystem owning mesh + strategy + grad-sync — instead of
string-passing schedule names.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig, TrainConfig
from repro.dist.partition import (batch_specs, cache_specs, param_specs,
                                  to_shardings)
from repro.launch.mesh import batch_axes
from repro.models import transformer as T
from repro.optim import AdamState, adam_init, adam_update


# ----------------------------------------------------------- input specs ---
def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)
    i32 = jnp.int32
    if shape.mode == "decode":
        return {"token": jax.ShapeDtypeStruct((B,), i32),
                "pos": jax.ShapeDtypeStruct((B,), i32)}
    if cfg.frontend == "audio":
        return {"features": jax.ShapeDtypeStruct((B, S, cfg.frontend_feat_dim), dt),
                "mask": jax.ShapeDtypeStruct((B, S), jnp.bool_),
                "targets": jax.ShapeDtypeStruct((B, S), i32)}
    if cfg.frontend == "vision":
        Tt = S - cfg.num_patches
        return {"tokens": jax.ShapeDtypeStruct((B, Tt), i32),
                "labels": jax.ShapeDtypeStruct((B, Tt), i32),
                "patches": jax.ShapeDtypeStruct(
                    (B, cfg.num_patches, cfg.frontend_feat_dim), dt)}
    return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32)}


def abstract_train_state(cfg: ModelConfig):
    params = T.init_abstract(cfg)
    opt = jax.eval_shape(adam_init, params)
    return params, opt


def abstract_cache(cfg: ModelConfig, shape: InputShape,
                   window_override: Optional[int] = None,
                   per_layer: bool = False):
    return jax.eval_shape(
        functools.partial(T.init_cache, cfg, shape.global_batch,
                          shape.seq_len, window_override,
                          per_layer=per_layer))


# ------------------------------------------------------------- shardings ---
def _act_spec(mesh, mode: str, kind: str = "dmodel"):
    bt = batch_axes(mesh)
    ax = bt if len(bt) > 1 else bt[0]
    if kind == "none" or mode == "decode":
        return None
    if kind == "seq":
        return P(ax, "model", None)
    return P(ax, None, "model")


def make_train_step(cfg: ModelConfig, mesh, shape: InputShape,
                    train_cfg: TrainConfig = TrainConfig(),
                    lgr: str = "har", act_sharding: str = "dmodel",
                    moe_spec: str = "contract"):
    """Returns (jitted_fn, example_args (SDS), arg_shardings)."""
    fsdp = (lgr == "har")
    params_sds, opt_sds = abstract_train_state(cfg)
    pspecs = param_specs(params_sds, mesh, fsdp=fsdp, moe_spec=moe_spec)
    ospecs = AdamState(step=P(),
                       mu=param_specs(params_sds, mesh, fsdp=fsdp,
                                      moe_spec=moe_spec),
                       nu=param_specs(params_sds, mesh, fsdp=fsdp,
                                      moe_spec=moe_spec))
    batch_sds = input_specs(cfg, shape)
    bspecs = batch_specs(batch_sds, mesh, batch_axes=batch_axes(mesh))
    T.set_activation_sharding(_act_spec(mesh, shape.mode, act_sharding))
    from repro.models.moe import set_moe_sharding
    bt = batch_axes(mesh)
    set_moe_sharding(bt if len(bt) > 1 else bt[0])

    M = max(train_cfg.microbatches, 1)

    def train_step(params, opt_state, batch):
        def loss_of(b):
            return lambda p: T.loss_fn(p, cfg, b, remat=train_cfg.remat)

        if M == 1:
            lval, grads = jax.value_and_grad(
                lambda p: T.loss_fn(p, cfg, batch,
                                    remat=train_cfg.remat))(params)
        else:
            # gradient accumulation: scan over M microbatches; activation
            # memory scales 1/M, gradient-sync bytes unchanged (one sync)
            mb = jax.tree.map(
                lambda x: x.reshape((M, x.shape[0] // M) + x.shape[1:]),
                batch)
            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def mb_step(carry, b):
                acc, ltot = carry
                lv, g = jax.value_and_grad(
                    lambda p: T.loss_fn(p, cfg, b,
                                        remat=train_cfg.remat))(params)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32) / M, acc, g)
                return (acc, ltot + lv / M), None

            (grads, lval), _ = jax.lax.scan(mb_step,
                                            (acc0, jnp.float32(0.0)), mb)
        params, opt_state = adam_update(
            grads, opt_state, params, lr=train_cfg.learning_rate,
            beta1=train_cfg.beta1, beta2=train_cfg.beta2,
            weight_decay=train_cfg.weight_decay,
            grad_clip=train_cfg.grad_clip)
        return params, opt_state, {"loss": lval.astype(jnp.float32)}

    fn = jax.jit(
        train_step,
        in_shardings=(to_shardings(pspecs, mesh),
                      to_shardings(ospecs, mesh),
                      to_shardings(bspecs, mesh)),
        out_shardings=(to_shardings(pspecs, mesh),
                       to_shardings(ospecs, mesh),
                       NamedSharding(mesh, P())),
        donate_argnums=(0, 1))
    return fn, (params_sds, opt_sds, batch_sds)


def make_prefill_step(cfg: ModelConfig, mesh, shape: InputShape,
                      window_override: Optional[int] = None,
                      act_sharding: str = "dmodel"):
    params_sds = T.init_abstract(cfg)
    pspecs = param_specs(params_sds, mesh, fsdp=False)
    batch_sds = input_specs(cfg, shape)
    bspecs = batch_specs(batch_sds, mesh, batch_axes=batch_axes(mesh))
    cache_sds = abstract_cache(cfg, shape, window_override)
    cspecs = cache_specs(cache_sds, mesh,
                         batch_shardable=shape.global_batch > 1)
    T.set_activation_sharding(_act_spec(mesh, shape.mode, act_sharding))
    from repro.models.moe import set_moe_sharding
    bt = batch_axes(mesh)
    set_moe_sharding(bt if len(bt) > 1 else bt[0])

    def prefill_step(params, batch):
        logits, caches = T.prefill(params, cfg, batch, shape.seq_len,
                                   window_override)
        return logits.astype(jnp.float32), caches

    fn = jax.jit(
        prefill_step,
        in_shardings=(to_shardings(pspecs, mesh),
                      to_shardings(bspecs, mesh)),
        out_shardings=(NamedSharding(mesh, P()),
                       to_shardings(cspecs, mesh)))
    return fn, (params_sds, batch_sds)


def make_serve_step(cfg: ModelConfig, mesh, shape: InputShape,
                    window_override: Optional[int] = None,
                    cache_layout: str = "heads", params_fsdp: bool = False,
                    unroll: bool = False, per_layer_cache: bool = False):
    """One decode step over a seq_len-deep KV/state cache."""
    per_layer_cache = per_layer_cache and cfg.local_global \
        and not cfg.block_pattern
    unroll = unroll or per_layer_cache
    params_sds = T.init_abstract(cfg)
    pspecs = param_specs(params_sds, mesh, fsdp=params_fsdp)
    cache_sds = abstract_cache(cfg, shape, window_override,
                               per_layer=per_layer_cache)
    cspecs = cache_specs(cache_sds, mesh,
                         batch_shardable=shape.global_batch > 1,
                         layout=cache_layout)
    tok_sds = input_specs(cfg, shape)
    bspecs = batch_specs(tok_sds, mesh, batch_axes=batch_axes(mesh))
    T.set_activation_sharding(None)
    from repro.models.moe import set_moe_sharding
    bt = batch_axes(mesh)
    nb = 1
    for a, s in zip(mesh.axis_names, mesh.axis_sizes):
        if a in bt:
            nb *= s
    set_moe_sharding((bt if len(bt) > 1 else bt[0])
                     if shape.global_batch % nb == 0 else None)

    def serve_step(params, caches, token, pos):
        logits, caches = T.decode_step(params, cfg, token, pos, caches,
                                       window_override,
                                       unroll=unroll and not cfg.block_pattern)
        return logits.astype(jnp.float32), caches

    fn = jax.jit(
        serve_step,
        in_shardings=(to_shardings(pspecs, mesh),
                      to_shardings(cspecs, mesh),
                      to_shardings(bspecs["token"], mesh),
                      to_shardings(bspecs["pos"], mesh)),
        out_shardings=(NamedSharding(mesh, P()),
                       to_shardings(cspecs, mesh)),
        donate_argnums=(1,))
    return fn, (params_sds, cache_sds, tok_sds["token"], tok_sds["pos"])


# ------------------------------------------------------------- DRL steps ---
# The DRL layer's launch entry points, mirroring the LLM builders above:
# the launcher (not the algorithm module) decides which hot path a step
# compiles to and how the experience pipeline is laid out over GMIs.

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


def make_drl_train_step(env, ppo_cfg=None, grad_sync_fn=None,
                        fused: Optional[bool] = None, communicator=None):
    """Jitted sync-PPO iteration with the fused Pallas hot path on by
    default: the gae_scan kernel (GAE + advantage normalization in one
    VMEM pass).  An explicit
    ``ppo_cfg`` keeps its own ``use_fused_kernels`` unless ``fused``
    explicitly overrides it.  Gradient sync comes from ``communicator``
    (a ``repro.comm.Communicator``) when given, else ``grad_sync_fn``."""
    from repro.rl.ppo import PPOConfig, make_train_step
    cfg = ppo_cfg if ppo_cfg is not None \
        else PPOConfig(use_fused_kernels=True)
    if fused is not None and fused != cfg.use_fused_kernels:
        cfg = cfg._replace(use_fused_kernels=fused)
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
    sync = communicator if communicator is not None else grad_sync_fn
    return make_train_step(env, cfg, sync), cfg


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
