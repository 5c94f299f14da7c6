"""Partition-spec rules for a ``(data, model)`` or ``(pod, data, model)`` mesh.

Specs are derived per-leaf from the leaf's tree path and shape, never from a
per-architecture table, so every config in ``repro.configs.ARCHS`` shards
without registration:

* ``model`` (tensor-parallel) goes on the trailing feature dim of every
  matrix whose size divides the axis — and on the vocab dim of embedding-like
  tables (vocab-parallel).  When the vocab does not divide the axis (granite's
  49155) the table falls back to replication on ``model`` rather than
  crashing or padding (GSPMD's gather-of-sharded-table path is also buggy on
  ragged shards, so replication is the safe fallback).
* ``data`` (FSDP) goes on the first remaining dim that divides the axis —
  the stacked-layer dim when the depth divides, else the input-feature dim.
  Only applied when ``fsdp=True`` (the HAR layout); the MRR layout keeps
  params replicated so gradient sync lowers to one flat ring.
* 1-D leaves (biases, norm scales) and scalars are replicated — sharding
  them saves nothing and forces per-layer all-gathers.

Every rule is guarded by divisibility: an axis is only ever assigned to a
dim whose size it divides, so any mesh/arch combination yields a valid
(possibly partially-replicated) sharding instead of an error.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_EMBED_KEYS = ("embed", "unembed", "table", "head")


def _axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        out.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return tuple(out)


def _is_spec_leaf(x) -> bool:
    return isinstance(x, P)


def to_shardings(spec_tree, mesh):
    """PartitionSpec tree -> NamedSharding tree on a concrete mesh."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=_is_spec_leaf)


# ---------------------------------------------------------------- params ---
def param_specs(params_sds, mesh, fsdp: bool = False,
                moe_spec: str = "contract"):
    """PartitionSpec per parameter leaf (see module docstring for rules).

    ``moe_spec``: "contract" shards expert matrices on their feature dims
    (generic rule); "expert" prefers the expert-count dim for ``model``.
    """
    sizes = _axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    data_n = sizes.get("data", 1)

    def one(path, sds):
        shape = sds.shape
        nd = len(shape)
        if nd == 0:
            return P()
        names = _path_names(path)
        if nd == 1:
            return P()
        axes = [None] * nd

        if any(n in _EMBED_KEYS for n in names):
            # vocab-parallel: the vocab dim is the larger of the two
            vdim = 0 if shape[0] >= shape[-1] else nd - 1
            if model_n > 1 and shape[vdim] % model_n == 0:
                axes[vdim] = "model"
            if fsdp and data_n > 1:
                other = nd - 1 if vdim == 0 else 0
                if axes[other] is None and shape[other] % data_n == 0:
                    axes[other] = "data"
            return P(*axes)

        if moe_spec == "expert" and "moe" in names and nd >= 3 \
                and model_n > 1 and shape[1] % model_n == 0:
            axes[1] = "model"
        elif model_n > 1:
            for d in (nd - 1, nd - 2):
                if shape[d] % model_n == 0:
                    axes[d] = "model"
                    break
        if fsdp and data_n > 1:
            for d in range(nd):
                if axes[d] is None and shape[d] % data_n == 0:
                    axes[d] = "data"
                    break
        return P(*axes)

    return jax.tree_util.tree_map_with_path(one, params_sds)


# ---------------------------------------------------------------- batches --
def batch_specs(batch_sds, mesh, batch_axes: Sequence[str] = ("data",)):
    """Shard the leading (global-batch) dim over the batch axes."""
    sizes = _axis_sizes(mesh)
    bt = tuple(a for a in batch_axes if sizes.get(a, 1) > 1) or \
        tuple(batch_axes)
    n = 1
    for a in bt:
        n *= sizes.get(a, 1)
    ax = bt if len(bt) > 1 else bt[0]

    def one(sds):
        if len(sds.shape) == 0 or sds.shape[0] % n != 0:
            return P()
        return P(*([ax] + [None] * (len(sds.shape) - 1)))

    return jax.tree.map(one, batch_sds)


# ---------------------------------------------------------------- caches ---
def cache_specs(cache_sds, mesh, batch_shardable: bool = True,
                layout: str = "heads"):
    """Specs for stacked decode caches (leading dim = layers/super-blocks).

    ``layout="heads"``: KV tensors (layers, B, S, n_kv, hd) shard the
    head-count dim over ``model`` when it divides (TP-style serving);
    ``layout="batch"`` leaves heads replicated.  The batch dim (index 1
    after the layer stack) shards over ``data`` when allowed.  SSM state
    leaves (rank < 4) only ever shard their batch dim — recurrent state
    dims must stay intact on one chip.
    """
    sizes = _axis_sizes(mesh)
    model_n = sizes.get("model", 1)
    data_n = sizes.get("data", 1)

    def one(sds):
        shape = sds.shape
        nd = len(shape)
        if nd < 2:
            return P()
        axes = [None] * nd
        bdim = 1 if nd >= 3 else 0     # leading layer-stack dim when rank>=3
        if batch_shardable and data_n > 1 and shape[bdim] % data_n == 0:
            axes[bdim] = "data"
        if layout == "heads" and nd >= 4 and model_n > 1 \
                and shape[nd - 2] % model_n == 0:
            axes[nd - 2] = "model"
        return P(*axes)

    return jax.tree.map(one, cache_sds)
