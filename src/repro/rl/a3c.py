"""A3C-style asynchronized DRL training (Mnih et al., ICML'16; GA3C).

The paper's async mode decouples *serving* (experience collection on agent
GMIs) from *training* (policy update on trainer GMIs), connected by the
channel-based experience pipeline (§4.2).  In single-controller JAX the
asynchrony is modeled as round-interleaved execution with an explicit
parameter-staleness counter: actors hold a possibly-stale snapshot of the
policy; trainers consume experience batches in arrival order.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.policy import entropy, log_prob, policy_apply
from repro.optim import adam_update
from repro.rl.rollout import collect, collect_ring
from repro.spans import span


class Experience(NamedTuple):
    """One actor-produced experience batch (the unit shipped over channels)."""
    obs: jax.Array        # (T, N, obs_dim)
    actions: jax.Array    # (T, N, act_dim)
    rewards: jax.Array    # (T, N)
    dones: jax.Array      # (T, N)
    bootstrap: jax.Array  # (N,) value of last obs under the actor's params
    actor_version: jax.Array  # scalar: params version used to act


def actor_collect(params, version, env, env_state, obs, key,
                  num_steps: int) -> tuple:
    """Experience collection on an agent instance (policy serving)."""
    traj, env_state, obs, last_value, key = collect(
        params, env, env_state, obs, key, num_steps)
    exp = Experience(obs=traj.obs, actions=traj.actions, rewards=traj.rewards,
                     dones=traj.dones, bootstrap=last_value,
                     actor_version=version)
    return exp, env_state, obs, key


def nstep_returns(rewards, dones, bootstrap, gamma: float = 0.99, *,
                  use_fused_kernels: bool = False):
    """Reverse discounted-return scan; ``use_fused_kernels`` routes it
    through the fused Pallas block-resident scan (kernels/gae_scan.py's
    n-step sibling) instead of the unfused ``lax.scan``."""
    if use_fused_kernels:
        from repro.kernels import ops
        return ops.nstep_returns(rewards, dones, bootstrap, gamma=gamma)

    def step(carry, xs):
        r, d = xs
        g = r + gamma * carry * (1.0 - d)
        return g, g
    _, rets = jax.lax.scan(step, bootstrap, (rewards, dones), reverse=True)
    return rets


def a3c_loss(params, exp: Experience, gamma: float, vf_coef: float,
             ent_coef: float, use_fused_kernels: bool = False):
    """The A3C loss and its gradient in ``params``:
    ``((loss, (pg, vf, ent)), grads)``.

    The n-step returns depend on the experience alone, so they are
    computed before the gradient is traced: under a jit the returns
    kernel stays its own ``nstep_returns`` call on HBM operands instead
    of a JVP of it."""
    rets = nstep_returns(exp.rewards, exp.dones, exp.bootstrap, gamma,
                         use_fused_kernels=use_fused_kernels)

    def loss(p):
        mu, log_std, value = policy_apply(p, exp.obs)
        adv = rets - value
        lp = log_prob(mu, log_std, exp.actions)
        pg = -(lp * jax.lax.stop_gradient(adv)).mean()
        vf = 0.5 * jnp.square(adv).mean()
        ent = entropy(log_std).mean()
        return pg + vf_coef * vf - ent_coef * ent, (pg, vf, ent)
    return jax.value_and_grad(loss, has_aux=True)(params)


def trainer_update(params, opt_state, exp: Experience, *, lr=3e-4,
                   gamma=0.99, vf_coef=0.5, ent_coef=0.01, grad_sync_fn=None,
                   max_grad_norm=1.0, use_fused_kernels=False):
    """Policy update on a trainer instance from one experience batch.

    ``grad_sync_fn`` may be a bare closure or a
    ``repro.comm.Communicator`` (resolved via its grad-sync property)."""
    from repro.comm.api import as_grad_sync
    grad_sync_fn = as_grad_sync(grad_sync_fn)
    (loss, aux), grads = a3c_loss(params, exp, gamma, vf_coef, ent_coef,
                                  use_fused_kernels)
    if grad_sync_fn is not None:
        grads = grad_sync_fn(grads)
    params, opt_state = adam_update(grads, opt_state, params, lr=lr,
                                    beta1=0.9, beta2=0.999,
                                    grad_clip=max_grad_norm)
    return params, opt_state, loss


def staleness(current_version, exp: Experience):
    """Paper §5.1: async training trades throughput for parameter staleness."""
    return current_version - exp.actor_version


class AsyncRunner:
    """Round-interleaved async A3C over the device-resident MCC pipeline.

    Owns the whole §4.2 flow for one async layout: serving GMIs collect
    with a (possibly stale) parameter snapshot, pushes land in the
    per-group device ring buffers, ``flush`` pointer-bumps the round's
    experience to the trainers the Migrator picks, and every consumed
    batch advances the parameter version.  The per-GMI GPU map from the
    placement layout is what lets the Migrator direct-forward same-GPU
    groups instead of funneling every flush to one trainer.

    ``overlap=True`` double-buffers the rings (paper §4.1): ``flush``
    swaps buffers instead of waiting, so each round trains on the
    PREVIOUS round's experience while this round's pushes are still
    materializing in the front halves — serving never stalls behind the
    trainer.  Call :meth:`finish` when done so the in-flight tail is
    trained on too (``trained_samples`` catches up to ``predictions``
    there, at the cost of one extra staleness step on the tail).

    An attached :class:`~repro.core.controller.OnlineGMIController`
    observes every round (throughput, ring occupancy, spills) and may
    hand back a re-plan between epochs; :meth:`replan` applies it by
    draining the old pipeline (lossless across the re-plan), rebuilding
    pipeline + actors under the new layout, and keeping model state.

    An attached :class:`~repro.comm.Communicator` owns the reduction
    decision state for the controller loop: measured per-round reduce
    seconds reach it through ``RoundSample.reduce_s`` (or direct
    ``observe`` calls from a real SPMD launcher — the runner has no
    cross-instance reduce to time, and timing the identity closure would
    feed scheduler noise into the switch hysteresis), and a controller
    Decision carrying a ``reduction_strategy`` switches the schedule in
    place — communication plumbing only, params/optimizer untouched.
    Mesh-attached communicators are rejected: their sync closure is
    SPMD-only and cannot run inside this one-device trainer.

    Each batch's update is one compiled program (:func:`trainer_update`
    under ``jax.jit``), traced once per batch shape; ``update_traces``
    counts the traces.
    """

    def __init__(self, env, serving_gmis, trainer_gmis, *, gmi_gpu=None,
                 num_envs: int = 64, num_steps: int = 16, seed: int = 0,
                 lr: float = 3e-4, pipeline=None, overlap: bool = False,
                 controller=None, layout_builder=None, communicator=None,
                 router=None, use_fused_kernels: bool = False):
        from repro.core.channels import MultiChannelPipeline
        from repro.models.policy import init_policy
        from repro.optim import adam_init

        self.env = env
        self.num_steps = num_steps
        self.num_envs = num_envs
        self.serving_gmis = list(serving_gmis)
        self.seed = seed
        self.overlap = overlap
        self.controller = controller
        self.layout_builder = layout_builder
        # single-arbiter control plane: with a request-serving front
        # attached (RequestRouter or serve.disagg.DisaggFront), its
        # telemetry epochs fold into the SAME controller instance every
        # round and its decisions apply through the front's thin
        # apply_decision hook — rollout, trainer, prefill, and decode
        # GMIs all arbitrated by one Algorithm-2 loop under one
        # min_gain hysteresis, never by a second decision loop
        self.router = router
        if communicator is not None and communicator.mesh is not None:
            raise TypeError(
                "AsyncRunner's round-interleaved trainer runs on one "
                "device; a mesh-attached Communicator's sync closure is "
                "SPMD-only (use allreduce in a shard_map launcher, or "
                "attach a mesh-less Communicator for decision state)")
        self.communicator = communicator
        if controller is not None and communicator is not None \
                and controller.communicator is None:
            controller.communicator = communicator
        self.pipe = pipeline or MultiChannelPipeline(
            serving_gmis, trainer_gmis, gmi_gpu=gmi_gpu, overlap=overlap)
        self.params = init_policy(jax.random.key(seed), env.spec.policy_dims)
        self.opt_state = adam_init(self.params)
        self.actor_params = self.params        # stale snapshot
        self.version = jnp.int32(0)
        self.actors = {}
        self._reset_actors()
        self.predictions = 0
        self.trained_samples = 0
        self.replans = 0
        self.rounds = 0
        # fault-injection seam (repro.fault): called with ("serving", gmi)
        # before each actor collect and ("trainer", gmi) before each batch
        # update; raising InjectedFault there kills that GMI mid-round.
        # The trainer path re-queues every consumed-but-untrained batch
        # into the pipeline (spill-not-drop) before propagating.
        self.fault_hook = None
        # non-finite guard (installed by the FleetSupervisor): a batch
        # whose loss is NaN/inf — e.g. a poisoned channel flush — has its
        # UPDATE discarded (params/opt/version untouched) instead of
        # corrupting the model; the data itself is unrecoverable and is
        # counted, not retrained
        self.nonfinite_guard = False
        self.poisoned_batches = 0
        self.poisoned_samples = 0
        # one compiled program per batch update.  Built per runner, and
        # trainer_update is looked up when traced, so a patched
        # trainer_update is the one compiled.  It takes no sync closure:
        # a mesh-less communicator's is the identity.  Nothing is
        # donated: the non-finite guard's rollback needs the old pytrees.
        self.update_traces = 0

        def update(params, opt_state, exp):
            self.update_traces += 1
            return trainer_update(params, opt_state, exp, lr=lr,
                                  use_fused_kernels=use_fused_kernels)
        self._update = jax.jit(update)

    def _reset_actors(self):
        self.actors = {}
        for a in self.serving_gmis:
            es, obs = self.env.reset(jax.random.PRNGKey(self.seed + a),
                                     num_envs=self.num_envs)
            self.actors[a] = [es, obs,
                              jax.random.PRNGKey(self.seed + 100 + a)]

    # repro: hot
    def _train(self, routed):
        """Consume routed trainer batches; returns (losses, staleness)."""
        with span("a3c.train"):
            losses, stale = [], []
            # flat worklist so a mid-iteration trainer fault can re-queue
            # the failing batch AND everything not yet consumed
            work = [(dst, exp) for dst, batches in routed.items()
                    for exp in batches]
            for i, (dst, exp) in enumerate(work):
                if self.fault_hook is not None:
                    try:
                        self.fault_hook("trainer", dst)
                    except BaseException:
                        # spill, not drop: this batch's gradient is lost
                        # with the trainer, but its experience — and every
                        # batch behind it — rejoins the pipeline for the
                        # survivors
                        self.pipe.requeue([e for _, e in work[i:]])
                        raise
                with span("host_read"):
                    stale.append(int(staleness(self.version, exp)))
                with span("a3c.update"):
                    new_params, new_opt, loss = self._update(
                        self.params, self.opt_state, exp)
                if self.nonfinite_guard:
                    with span("host_read"):
                        finite = bool(jnp.isfinite(loss))
                    if not finite:
                        # discard the poisoned update: the pre-update
                        # pytrees are still live (JAX arrays are immutable —
                        # rollback is free); version stays put so staleness
                        # accounting is untouched
                        self.poisoned_batches += 1
                        self.poisoned_samples += int(exp.rewards.size)
                        continue
                self.params, self.opt_state = new_params, new_opt
                # keep the loss on device: a float() here would sync the
                # trainer stream once per batch (host-sync-in-hot-path)
                losses.append(loss)
                self.trained_samples += int(exp.rewards.size)
                self.version = self.version + 1
            if not losses:
                return [], stale
            # single post-loop drain of the queued losses
            with span("host_read"):
                losses = jax.device_get(losses)
            return [float(x)  # repro: allow(host-sync-in-hot-path)
                    for x in losses], stale

    # repro: hot
    def _serve(self, a, direct):
        """Serving GMI ``a`` rolls its envs for one round into the
        pipeline."""
        es, obs, k = self.actors[a]
        if direct:
            carry = {}

            def producer(bufs, slot):
                bufs, es2, obs2, boot, k2 = collect_ring(
                    self.actor_params, self.env, es, obs, k,
                    self.num_steps, bufs, slot)
                carry["actor"] = [es2, obs2, k2]
                return bufs, boot, self.version

            self.pipe.produce(a, self.num_steps, self.num_envs,
                              self.env.spec.obs_dim,
                              self.env.spec.act_dim, producer)
            self.actors[a] = carry["actor"]
            self.predictions += self.num_steps * self.num_envs
            return
        exp, es, obs, k = actor_collect(
            self.actor_params, self.version, self.env, es, obs, k,
            self.num_steps)
        self.actors[a] = [es, obs, k]
        self.predictions += int(exp.rewards.size)
        self.pipe.push(a, exp)

    # repro: hot
    def round(self):
        """One serve -> ship -> train round; returns (losses, staleness).

        With overlap on, the trained batches are the previous round's
        flush (the first round returns no losses)."""
        # megakernel envs on blocking rings produce experience straight
        # into the ring slot (collect_ring): no staged Trajectory, no
        # pack_channels re-copy.  Overlap rings stage references (zero
        # producer-side device work already), so they keep actor_collect.
        direct = (getattr(self.env, "megakernel", False)
                  and not self.overlap and hasattr(self.pipe, "produce"))
        before = self.trained_samples
        # the round span's duration is the round time the controller's
        # ladder observes
        with span("a3c.round", round=self.rounds) as timed:
            for a in self.serving_gmis:
                if self.fault_hook is not None:
                    # a kill here loses only THIS GMI's not-yet-collected
                    # round; earlier actors' pushes are already ringed and
                    # survive into the recovery drain
                    self.fault_hook("serving", a)
                with span("a3c.serve", gmi=a):
                    self._serve(a, direct)
            losses, stale = self._train(self.pipe.flush())
            self.actor_params = self.params    # model push AFTER acting
        if self.controller is not None:
            decision = self.controller.observe_pipeline(
                self.pipe, samples=self.trained_samples - before,
                dt=timed.seconds)
            if decision is not None:
                if decision.layout_changed:
                    self.replan(decision)
                elif decision.reduction_strategy \
                        and self.communicator is not None:
                    # strategy-only re-plan: pure communication plumbing,
                    # no pipeline drain / actor rebuild needed
                    self.communicator.switch(decision.reduction_strategy)
        if self.router is not None and self.controller is not None:
            # the serving half of the single-arbiter loop: fold the
            # front's telemetry epoch into the same controller and apply
            # whatever it answers through the thin hook.  A decision
            # captured before this round's rollout re-plan carries a
            # stale seq and is refused by the hook's fence.
            sdec = self.controller.observe_serving(self.router.take_epoch())
            if sdec is not None:
                self.router.apply_decision(sdec, controller=self.controller)
        self.rounds += 1
        return losses, stale

    def finish(self):
        """Drain the pipeline (both buffer halves in overlap mode) and
        train on the tail; returns (losses, staleness)."""
        losses, stale = self._train(self.pipe.drain())
        self.actor_params = self.params
        return losses, stale

    def replan(self, decision, layout=None):
        """Apply a controller Decision between epochs: drain + train on
        everything still buffered (nothing is lost across the re-plan),
        then rebuild the pipeline — carrying the old pipeline's batching
        /ring/backend configuration — and the actors under the new
        layout.  Model parameters, optimizer state, and version persist.
        A decision carrying a ``reduction_strategy`` additionally switches
        the communicator's LGR schedule in place — by construction this
        touches no model state.

        An explicit ``layout`` bypasses the controller/layout_builder —
        the FleetSupervisor's failure-recovery path, where the layout is
        planned against the reduced (quarantined) pool rather than the
        controller's notion of the fleet."""
        if not hasattr(self.pipe, "clone_for"):
            raise TypeError(
                f"online re-planning needs a pipeline with clone_for "
                f"(MultiChannelPipeline), got {type(self.pipe).__name__}")
        if self.controller is not None:
            # staleness fence: any serving Decision emitted before this
            # drain carries the old seq and must not apply afterwards —
            # it was computed against the layout being torn down
            self.controller.plan_seq += 1
        self._train(self.pipe.drain())
        if layout is None:
            layout = (self.layout_builder(decision) if self.layout_builder
                      else self.controller.plan_layout())
        if self.communicator is not None:
            # the communicator's grid/cost model must track the NEW
            # layout, or later strategy decisions are scored (and
            # validated) against a stale instance grid
            self.communicator.rebind(layout)
            if getattr(decision, "reduction_strategy", None):
                strat = decision.reduction_strategy
                if strat in self.communicator.candidates():
                    self.communicator.switch(strat)
        gmi_gpu = {g.gmi_id: g.gpu_id for g in layout.manager.gmis.values()}
        self.serving_gmis = list(layout.serving_gmis)
        self.pipe = self.pipe.clone_for(layout.serving_gmis,
                                        layout.trainer_gmis, gmi_gpu=gmi_gpu)
        self.num_envs = int(decision.num_env)
        self._reset_actors()
        self.actor_params = self.params
        self.replans += 1
        return layout

    # ------------------------------------------------- preemption safety --
    def _ckpt_template(self):
        return {"params": self.params, "opt_state": self.opt_state,
                "version": self.version}

    def checkpoint(self, directory, step=None, fault_hook=None):
        """Preemption-safe checkpoint: params/opt_state/version as the
        atomic npz+manifest pair (``repro.checkpoint``), with counters and
        the controller's learned tables riding in the manifest ``extra``.
        Returns the checkpoint path prefix."""
        import os

        from repro.checkpoint import ckpt
        if step is None:
            step = int(self.version)
        extra = {"predictions": self.predictions,
                 "trained_samples": self.trained_samples,
                 "num_envs": self.num_envs,
                 "rounds": self.rounds}
        if self.controller is not None \
                and hasattr(self.controller, "state_dict"):
            extra["controller"] = self.controller.state_dict()
        path = os.path.join(directory, f"ckpt_{step}")
        ckpt.save(path, self._ckpt_template(), step=step, extra=extra,
                  fault_hook=fault_hook)
        return path

    def restore(self, directory, shardings=None):
        """Resume from the newest LOADABLE checkpoint in ``directory``.

        Torn pairs (manifest without npz) are invisible via
        ``ckpt.steps``; a pair that is present but unreadable (truncated
        npz, template mismatch) is skipped and the previous step is
        tried — so a crash during or after a save always resumes from the
        last durable state.  Returns the restored step, or ``None`` when
        nothing loadable exists (fresh start)."""
        import os

        from repro.checkpoint import ckpt
        for step in reversed(ckpt.steps(directory)):
            path = os.path.join(directory, f"ckpt_{step}")
            try:
                tree = ckpt.load(path, self._ckpt_template(),
                                 shardings=shardings)
                extra = ckpt.load_manifest(path).get("extra") or {}
            except (FileNotFoundError, ValueError, KeyError):
                continue
            self.params = tree["params"]
            self.opt_state = tree["opt_state"]
            self.version = tree["version"]
            self.actor_params = self.params
            self.predictions = int(extra.get("predictions",
                                             self.predictions))
            self.trained_samples = int(extra.get("trained_samples",
                                                 self.trained_samples))
            self.rounds = int(extra.get("rounds", self.rounds))
            new_envs = int(extra.get("num_envs", self.num_envs))
            if new_envs != self.num_envs:
                self.num_envs = new_envs
                self._reset_actors()
            if self.controller is not None and "controller" in extra \
                    and hasattr(self.controller, "load_state_dict"):
                self.controller.load_state_dict(extra["controller"])
            return step
        return None
