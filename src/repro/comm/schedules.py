"""Layout-aware gradient-reduction schedules — LGR (paper §4.1), N-level.

The paper's three schedules, generalized from the original 2-axis
(gpu, inst) instance grid to the hierarchical (gpu, inst, dev) meshes
``GMIManager.instance_mesh`` builds for multi-device GMIs:

* MPR  (multi-process reduction): stage every instance's gradient through
  host memory and reduce on CPU — generic, layout-agnostic, slow (paper
  Table 2: 2·(g·t−1)·Mp / (g·t·B1)).  Inside one SPMD program it
  degenerates to a flat reduce; the true host-staged variant is
  :func:`mpr_host`.
* MRR  (multi-ring reduction): one flat ring over all instances — a single
  ``psum`` over every mesh axis (paper: non-intersecting NCCL rings + a
  final ring; valid only when instances-per-GPU ≤ GPUs).
* HAR  (hierarchical reduction): reduce within the fast domain first, then
  across the slow domain on shrunken shards, then gather — expressed as
  ``psum_scatter(intra) → psum(inter) → all_gather(intra)``.  On a 3-axis
  mesh the intra domain is the merged ``(inst, dev)`` plane.
* HAR3 (3-level hierarchical reduction): the fast domain is itself
  hierarchical — chips inside one GMI (``dev``, fastest links) and GMIs on
  one GPU (``inst``) — so the reduce nests one more level:
  ``psum_scatter(dev) → psum_scatter(inst) → psum(gpu) →
  all_gather(inst) → all_gather(dev)``.  Cross-GPU traffic drops
  (inst·dev)×; cross-instance traffic drops dev×.

Sum-vs-mean semantics live in exactly ONE place: every schedule returns a
raw SUM; :func:`_finalize_average` applies the optional division, used by
:func:`make_grad_sync` (in-SPMD) and :func:`mpr_host` (host) alike.

The same schedules serve two scales:
  DRL GMIs   — ``dev`` = chips in one instance, ``inst`` = instances on one
               GPU, ``gpu`` = physical device groups;
  LLM pods   — intra axis = 'data' (ICI), inter axis = 'pod' (DCN).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

STRATEGIES = ("mpr", "mrr", "har", "har3")


# ----------------------------------------------------- average (one place) --
def _finalize_average(tree, count: int, average: bool):
    """THE single sum-vs-mean switch: every schedule produces raw sums and
    every public entry point funnels through here (``average=True`` divides
    by the participant count, ``False`` returns the sum untouched)."""
    if not average:
        return tree
    return jax.tree.map(lambda g: g / count, tree)


def _axis_count(axis_names) -> int:
    """Static participant count over ``axis_names`` inside an SPMD body."""
    n = 1
    for a in axis_names:
        n *= jax.lax.axis_size(a)
    return n


# ---------------------------------------------------------------- in-SPMD --
def flat_psum(grads, axis_names):
    """MRR analogue: one flat all-reduce (raw sum) over the merged axes."""
    return jax.tree.map(lambda g: jax.lax.psum(g, tuple(axis_names)), grads)


def hierarchical_psum(grads, axes: Sequence):
    """N-level HAR (raw sum).  ``axes[0]`` is the slow reduce axis (plain
    ``psum``); ``axes[1:]`` are scatter levels ordered slow → fast, each a
    mesh-axis name or a tuple of names (a merged domain).

    Scatters apply fastest level first, gathers undo them in reverse:
    the 3-level form over ``("gpu", "inst", "dev")`` is exactly
    ``psum_scatter(dev) → psum_scatter(inst) → psum(gpu) →
    all_gather(inst) → all_gather(dev)``.  Operates leaf-wise on flattened
    gradients (padded to the product of scatter-level sizes) so arbitrary
    parameter shapes work.
    """
    reduce_axis = axes[0]
    levels = [tuple(a) if isinstance(a, (tuple, list)) else (a,)
              for a in axes[1:]]
    if not levels:
        return jax.tree.map(lambda g: jax.lax.psum(g, reduce_axis), grads)
    sizes = [_axis_count(lvl) for lvl in levels]
    block = int(np.prod(sizes))

    def one(g):
        shape = g.shape
        flat = g.reshape(-1)
        n = flat.shape[0]
        pad = (-n) % block
        flat = jnp.pad(flat, (0, pad))
        shard = flat
        for lvl, s in zip(reversed(levels), reversed(sizes)):   # fast first
            shard = jax.lax.psum_scatter(shard.reshape(s, -1), lvl,
                                         scatter_dimension=0, tiled=False)
        shard = jax.lax.psum(shard, reduce_axis)
        for lvl in levels:                  # undo scatters in reverse order
            shard = jax.lax.all_gather(shard, lvl, axis=0,
                                       tiled=False).reshape(-1)
        return shard[:n].reshape(shape)

    return jax.tree.map(one, grads)


def make_grad_sync(strategy: str, axes: Sequence[str] = ("gpu", "inst"),
                   *, average: bool = True) -> Callable:
    """Gradient-sync closure usable inside shard_map/pjit-SPMD bodies.

    ``axes`` is the instance grid ordered slow → fast (mesh axis order),
    e.g. ``("gpu", "inst")`` or ``("gpu", "inst", "dev")``.  ``average``
    divides the reduced sum by the total participant count — handled here
    (via :func:`_finalize_average`), never inside a schedule.
    """
    axes = tuple(axes)
    if len(axes) < 2:
        raise ValueError(
            f"LGR schedules need at least a 2-axis (inter, intra) instance "
            f"grid; got axes {axes}")
    if strategy in ("mrr", "mpr"):
        # inside an SPMD program MPR degenerates to a flat reduce; the true
        # host-staged variant is ``mpr_host`` below (submesh backend)
        sync_sum = functools.partial(flat_psum, axis_names=axes)
    elif strategy == "har":
        intra = axes[1] if len(axes) == 2 else tuple(axes[1:])
        sync_sum = functools.partial(hierarchical_psum,
                                     axes=(axes[0], intra))
    elif strategy == "har3":
        if len(axes) != 3:
            raise ValueError(
                f"har3 is the 3-level schedule and needs a 3-axis "
                f"(gpu, inst, dev) grid; got axes {axes} — use 'har' for "
                "2-level layouts")
        sync_sum = functools.partial(hierarchical_psum, axes=axes)
    else:
        raise ValueError(f"unknown reduction strategy {strategy!r}; "
                         f"expected one of {STRATEGIES}")
    if not average:
        return sync_sum

    def sync(grads):
        return _finalize_average(sync_sum(grads), _axis_count(axes), True)

    return sync


# ------------------------------------------------------------- host-staged -
def mpr_host(grads_per_instance: Sequence, *, average: bool = True):
    """True multi-process reduction for the submesh (MIG-like) backend:
    every instance's gradients are pulled to host, reduced on CPU, and the
    result is returned (to be device_put per instance by the caller).

    This is the paper's generic-but-slow baseline: O(g·t) host transfers
    and CPU-side arithmetic.  ``average`` follows the same single-switch
    semantics as every other schedule (:func:`_finalize_average`).
    """
    host_trees = [jax.tree.map(np.asarray, jax.device_get(g))
                  for g in grads_per_instance]
    total = jax.tree.map(lambda *xs: sum(xs), *host_trees)
    return _finalize_average(total, len(host_trees), average)


# -------------------------------------------------------------- shard_map --
def lgr_allreduce(grads, mesh: Mesh, strategy: str, *,
                  average: bool = True):
    """Run an LGR schedule over per-instance gradient replicas.

    ``grads`` leaves must carry a leading instance grid matching the mesh
    shape — ``(g, t, ...)`` on a (gpu, inst) mesh, ``(g, t, d, ...)`` on a
    (gpu, inst, dev) mesh — one gradient per instance.  Returns the
    reduced (averaged by default) gradient with the same leading grid
    (all replicas equal).
    """
    nd = mesh.devices.ndim
    if nd not in (2, 3):
        raise ValueError(
            f"LGR schedules reduce over a 2-axis (gpu, inst) or 3-axis "
            f"(gpu, inst, dev) instance grid; got axes {mesh.axis_names}")
    axes = mesh.axis_names
    sync = make_grad_sync(strategy, axes, average=average)
    spec = P(*axes)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: spec, grads),),
        out_specs=jax.tree.map(lambda _: spec, grads))
    def run(gs):
        local = jax.tree.map(lambda x: x[(0,) * nd], gs)
        red = sync(local)
        return jax.tree.map(lambda x: x[(None,) * nd], red)

    return run(grads)
