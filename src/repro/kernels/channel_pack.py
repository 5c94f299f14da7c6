"""Pallas ring-buffer packing kernel for the MCC experience pipeline.

The paper's Compressor (§4.2) raises transfer granularity by batching
per-channel payloads across agents.  The seed implementation staged every
push through host lists and re-materialized each channel with
``jnp.concatenate`` on every flush — the fine-grained-transfer pathology of
arXiv:2012.04210.  Here a push instead writes the agent's (T, N, ...) block
in place into a preallocated per-channel device ring buffer at a
slot-aligned column offset, so a flush degenerates to one pointer-bump
slice per channel.

Ring layout (S = ring slots, one slot per push):

    obs           (T, S*N, obs_dim)     slot s -> columns [s*N, (s+1)*N)
    actions       (T, S*N, act_dim)
    rewards       (T, S*N)
    dones         (T, S*N)
    bootstrap     (S, N)                slot s -> row s
    actor_version (S, 1)                slot s -> row s

A double-buffered ring (paper §4.1 serve/train overlap) alternates
storage *generations*: pushes stage device-resident payload references
(zero device work on the producer's critical path) and the buffer swap
packs the whole back generation slot-by-index in ONE fused, donation-free
dispatch (``pack_generation``), handing the result to the consumer while
the front generation keeps staging.  Two alternatives were measured and
rejected on the Table-8 workload:

* both buffers in one ``2*S``-slot allocation with swap = index flip —
  every swap slice-copies its half AND the next push donates buffers
  with in-flight snapshot reads, serializing producer behind consumer;
* per-push in-place packing into a fresh generation (this file's kernel,
  as used by the blocking ring) — each donating push must wait for the
  previous push's buffers to materialize, so with a trainer consume in
  flight the donation chain re-serializes serve behind train (donation
  of a buffer with a pending definition blocks at dispatch).

The staged-generation pack has no donation anywhere, so serving runs
ahead of the trainer's consumption limited only by ring capacity.

The four (T, N)-shaped channels are packed by ONE ``pallas_call`` tiled
over env blocks: the slot index rides the scalar prefetch into the
output index maps and every ring buffer is aliased input->output, so
the kernel writes only the slot's column block and never touches the
untouched slots.  On the CPU backend the identical program is lowered
through XLA ``dynamic_update_slice`` (``pack_channels_xla``) — donated and
jitted, so it is also an in-place pointer-bump where the runtime allows.

Slot-write contract (zero-copy producers)
-----------------------------------------
The layout above is a public contract, not a private detail of this
file: the env megakernel (``kernels/env_megakernel.py``, driven by
``rl.rollout.collect_ring`` through ``ChannelRing.acquire``/``commit``)
writes the four produced channels DIRECTLY — rollout step ``t`` into
ring slot ``s`` over envs ``[s*N, (s+1)*N)`` stores, at row ``t`` of
that column block, the observation the policy acted on, the RAW sampled
action (pre-clip; the env clips internally, trainers recompute
log-probs from what was sampled), the step reward, and ``done`` as
float32.  ``bootstrap`` row ``s`` and ``actor_version`` row ``s`` land
at commit time.  A producer-written slot is byte-identical to the same
push staged through :func:`pack_channels` — ``snapshot`` and every
consumer downstream cannot tell the two apart, which is exactly why the
staging copy can be skipped.  Anything changing this layout must move
producer and packer together.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHANNELS = ("obs", "actions", "rewards", "dones", "bootstrap",
            "actor_version")


def _as_payloads(payloads: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Normalize payload ranks: bootstrap (N,)->(1,N), version ()->(1,1)."""
    out = dict(payloads)
    out["bootstrap"] = jnp.asarray(payloads["bootstrap"]).reshape(1, -1)
    out["actor_version"] = jnp.asarray(
        payloads["actor_version"], jnp.int32).reshape(1, 1)
    return out


# ----------------------------------------------------------------- pallas --
def _env_block(N: int) -> int:
    """Env-axis block for ring writes: the widest multiple of 128 up to
    1024 dividing N (the lane rule of the (T, S*N) channels), else N."""
    for be in (1024, 512, 256, 128):
        if N % be == 0:
            return be
    return N


def _kernel(slot_ref, obs_p, act_p, rew_p, done_p, obs_i, act_i, rew_i,
            done_i, obs_o, act_o, rew_o, done_o):
    del slot_ref, obs_i, act_i, rew_i, done_i      # aliased to outputs
    obs_o[...] = obs_p[...]
    act_o[...] = act_p[...]

    @pl.when(pl.program_id(1) == 0)
    def _():                     # (T, block) columns: once per env block
        rew_o[...] = rew_p[...]
        done_o[...] = done_p[...]


def pack_channels(bufs: Dict[str, jax.Array], payloads: Dict[str, jax.Array],
                  slot, *, interpret: bool = False) -> Dict[str, jax.Array]:
    """Write one push into ring slot ``slot``; returns the updated rings.

    ``bufs``/``payloads`` are keyed by ``CHANNELS``; payload shapes are the
    per-push shapes (see module docstring).  ``slot`` is a traced int32 —
    no retrace per slot.

    Grid ``(env blocks, T)``: each step copies one ``(block, dim)`` row of
    obs/actions into the slot's column block, and the ``(T, block)``
    reward/done columns once per env block.  The ring inputs stay in HBM
    (``pl.ANY``), aliased to the outputs, so untouched slots survive and
    nothing but the written block passes through VMEM.  The one-row
    ``bootstrap``/``actor_version`` writes are sub-tile rows no block can
    address; they land by ``dynamic_update_slice`` in the same program.
    """
    pay = _as_payloads(payloads)
    T, N = pay["rewards"].shape
    be = _env_block(N)
    nb = N // be
    slot_arr = jnp.asarray(slot, jnp.int32).reshape(1)

    def rows(d):
        return pl.BlockSpec((None, be, d), lambda i, t, s: (t, i, 0))

    def ring_rows(d):
        return pl.BlockSpec((None, be, d),
                            lambda i, t, s: (t, s[0] * nb + i, 0))

    cols = pl.BlockSpec((T, be), lambda i, t, s: (0, i))
    ring_cols = pl.BlockSpec((T, be), lambda i, t, s: (0, s[0] * nb + i))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    O, A = pay["obs"].shape[2], pay["actions"].shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, T),
        in_specs=[rows(O), rows(A), cols, cols, hbm, hbm, hbm, hbm],
        out_specs=[ring_rows(O), ring_rows(A), ring_cols, ring_cols],
    )
    keys = ("obs", "actions", "rewards", "dones")
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(bufs[c].shape, bufs[c].dtype)
                   for c in keys],
        # alias indices count the scalar-prefetch operand
        input_output_aliases={5 + k: k for k in range(4)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(slot_arr, *[pay[c] for c in keys], *[bufs[c] for c in keys])
    out = dict(zip(keys, out))
    z = jnp.int32(0)
    s = slot_arr[0]
    for c in ("bootstrap", "actor_version"):
        out[c] = jax.lax.dynamic_update_slice(bufs[c], pay[c], (s, z))
    return out


# -------------------------------------------------------------------- xla --
def _pack_xla(bufs, payloads, slot):
    pay = _as_payloads(payloads)
    _, N = pay["rewards"].shape
    col = slot * N
    z = jnp.int32(0)
    return {
        "obs": jax.lax.dynamic_update_slice(bufs["obs"], pay["obs"],
                                            (z, col, z)),
        "actions": jax.lax.dynamic_update_slice(bufs["actions"],
                                                pay["actions"], (z, col, z)),
        "rewards": jax.lax.dynamic_update_slice(bufs["rewards"],
                                                pay["rewards"], (z, col)),
        "dones": jax.lax.dynamic_update_slice(bufs["dones"], pay["dones"],
                                              (z, col)),
        "bootstrap": jax.lax.dynamic_update_slice(bufs["bootstrap"],
                                                  pay["bootstrap"],
                                                  (slot, z)),
        "actor_version": jax.lax.dynamic_update_slice(bufs["actor_version"],
                                                      pay["actor_version"],
                                                      (slot, z)),
    }


pack_channels_xla = jax.jit(_pack_xla, donate_argnums=(0,))


def alloc_rings(payloads, slots: int):
    """Zero-filled ring buffers sized for ``slots`` pushes shaped like
    ``payloads`` (the module-docstring layout)."""
    pay = _as_payloads(payloads)
    T, N = pay["rewards"].shape
    return {
        "obs": jnp.zeros((T, slots * N) + pay["obs"].shape[2:],
                         pay["obs"].dtype),
        "actions": jnp.zeros((T, slots * N) + pay["actions"].shape[2:],
                             pay["actions"].dtype),
        "rewards": jnp.zeros((T, slots * N), pay["rewards"].dtype),
        "dones": jnp.zeros((T, slots * N), pay["dones"].dtype),
        "bootstrap": jnp.zeros((slots, N), pay["bootstrap"].dtype),
        "actor_version": jnp.zeros((slots, 1), jnp.int32),
    }


@functools.partial(jax.jit, static_argnames=("slots",))
def pack_channels_fresh(payloads, *, slots: int):
    """Allocate rings and write slot 0 in one fused dispatch (the first
    push after a full-ring flush — or after a double-buffer generation
    swap — hands its buffers to the consumer, so the ring starts over on
    fresh storage)."""
    return _pack_xla(alloc_rings(payloads, slots), payloads, jnp.int32(0))


# ------------------------------------------------------- generation pack ---
@functools.lru_cache(maxsize=None)
def _generation_packer(n: int):
    """Jitted bulk pack of ``n`` staged pushes into one contiguous
    generation (slot ``s`` -> the slot-aligned block, exactly the ring
    layout above) — one donation-free dispatch per buffer swap."""
    C = len(CHANNELS)

    def pack(*flat):
        per = [_as_payloads(dict(zip(CHANNELS, flat[i * C:(i + 1) * C])))
               for i in range(n)]

        def cat(c, axis):
            xs = [p[c] for p in per]
            return xs[0] if n == 1 else jnp.concatenate(xs, axis=axis)

        return {
            "obs": cat("obs", 1),
            "actions": cat("actions", 1),
            "rewards": cat("rewards", 1),
            "dones": cat("dones", 1),
            "bootstrap": cat("bootstrap", 0).reshape(-1),
            "actor_version": cat("actor_version", 0).reshape(-1),
        }

    return jax.jit(pack)


def pack_generation(staged) -> Dict[str, jax.Array]:
    """Pack a sequence of staged per-push payload dicts (oldest first)
    into one generation's channel arrays, in a single dispatch."""
    assert staged
    flat = [p[c] for p in staged for c in CHANNELS]
    return _generation_packer(len(staged))(*flat)


# ----------------------------------------------------- cache-payload pack ---
# Prefill/decode disaggregation ships a finished prefill cache (an
# arbitrary pytree: KV stacks, SSM windows, hybrid mixes) between GMIs.
# Shipping dozens of small leaves is exactly the fine-grained-transfer
# pathology the ring pack above exists to avoid, so a cache payload is
# flattened into ONE contiguous 1-D buffer per dtype (the coarse-grained
# unit the channel ring moves) and reassembled bit-exactly on the decode
# side.  Both directions are jitted once per (treedef, shapes, dtypes)
# structure — the serving engines reuse a fixed cache layout, so in
# steady state pack/unpack are single cached dispatches.

@functools.lru_cache(maxsize=None)
def _cache_packer(spec):
    dtypes = sorted({d for _, d in spec})

    def pack(*leaves):
        return tuple(
            jnp.concatenate([leaves[i].reshape(-1)
                             for i, (_, d) in enumerate(spec) if d == dt])
            for dt in dtypes)

    return jax.jit(pack), dtypes


@functools.lru_cache(maxsize=None)
def _cache_unpacker(spec):
    dtypes = sorted({d for _, d in spec})

    def unpack(*bufs):
        offs = {dt: 0 for dt in dtypes}
        leaves = []
        for shape, dt in spec:
            n = 1
            for s in shape:
                n *= s
            buf = bufs[dtypes.index(dt)]
            leaves.append(jax.lax.dynamic_slice_in_dim(
                buf, offs[dt], n).reshape(shape))
            offs[dt] += n
        return tuple(leaves)

    return jax.jit(unpack)


def pack_cache_payload(tree):
    """Flatten a cache pytree into per-dtype contiguous 1-D buffers.

    Returns ``(bufs, meta)`` where ``bufs`` is a tuple of device arrays
    (one per distinct dtype, dtype-sorted) and ``meta`` re-creates the
    pytree via :func:`unpack_cache_payload`.  Round-trip is bit-exact —
    no casting, just ravel + concatenate."""
    leaves, treedef = jax.tree.flatten(tree)
    spec = tuple((tuple(l.shape), str(jnp.asarray(l).dtype))
                 for l in leaves)
    pack, _ = _cache_packer(spec)
    return pack(*leaves), (treedef, spec)


def unpack_cache_payload(bufs, meta):
    """Inverse of :func:`pack_cache_payload`."""
    treedef, spec = meta
    leaves = _cache_unpacker(spec)(*bufs)
    return jax.tree.unflatten(treedef, leaves)


def cache_payload_bytes(bufs) -> int:
    """Wire size of a packed payload (sum over per-dtype buffers)."""
    return int(sum(b.size * b.dtype.itemsize for b in bufs))


# ----------------------------------------------- page-wise payload pruning ---
@functools.lru_cache(maxsize=None)
def _page_slicer(lo: int, hi: int):
    def run(*leaves):
        return tuple(jax.lax.slice_in_dim(l, lo, hi, axis=2) for l in leaves)
    return jax.jit(run)


def truncate_cache_pages(tree, used_tokens: int, page_size: int,
                         head_skip: int = 0):
    """Prune a B=1 prefill-cache payload to whole pages before migration.

    Full-depth attention leaves (duck-typed: nodes with a ``slot_pos``
    field whose sequence depth covers every written position) are sliced
    along the sequence axis to ``[head_skip*page_size,
    ceil(used_tokens/page_size)*page_size)`` — dropping the max_seq tail a
    monolithic payload would ship, plus the leading ``head_skip`` pages
    the destination already holds in its shared-prefix index.  The decode
    engine's paged splice scatters entries by their recorded ``slot_pos``,
    so pruning is position-safe by construction.  Ring-buffer (sliding
    window) leaves shorter than ``used_tokens`` and recurrent-state leaves
    ship whole — they are already fixed-size.
    """
    P = max(int(page_size), 1)
    hi = -(-max(int(used_tokens), 0) // P) * P
    lo = min(max(int(head_skip), 0) * P, hi)

    def is_kv(n):
        return hasattr(n, "slot_pos") and hasattr(n, "k")

    def prune(n):
        if not is_kv(n):
            return n
        S = n.k.shape[2]
        if S < used_tokens:      # ring buffer: indices are not positions
            return n
        h = min(hi, S)
        k, v, sp = _page_slicer(lo, h)(n.k, n.v, n.slot_pos)
        return type(n)(k, v, sp)

    return jax.tree.map(prune, tree, is_leaf=is_kv)
