"""Channel-based experience sharing — MCC (paper §4.2), device-resident.

Four services connect agent instances to trainer instances in async DRL:

* Dispenser (per agent)  — categorizes experience into per-field channels
  (state / action / reward / done / bootstrap) at collection granularity.
* Compressor (system)    — raises transfer granularity by batching channel
  payloads across agents into large contiguous moves.
* Migrator (system)      — routes channel payloads to trainers: direct
  forward when agent and trainer share a device group; least-loaded
  distribution otherwise.
* Batcher (per trainer)  — slices (small-batch, high update frequency) or
  stacks (large-batch, noise reduction) into training batches.

Ring-buffer design
------------------
The seed implementation staged every push through host-side Python lists
and re-materialized each channel with ``jnp.asarray`` + ``jnp.concatenate``
on every flush — O(agents x channels) host round-trips, exactly the
fine-grained-transfer pathology the paper (and arXiv:2012.04210) blames
for DRL throughput collapse.  The pipeline is now device-resident end to
end:

* Each agent *group* (agents sharing a GPU per ``gmi_gpu``; all agents
  when no placement is given) owns a :class:`ChannelRing` — preallocated
  per-channel device buffers with capacity ``slots x T x N`` samples
  (``slots`` = agents in the group), laid out so push ``s`` occupies the
  slot-aligned column block ``[s*N, (s+1)*N)``.
* ``push`` writes the agent's whole (T, N, ...) block in place via the
  Pallas ``pack_channels`` kernel (one launch packs all six channels; ring
  buffers are donated/aliased).  Off-TPU the identical program lowers
  through a jitted, donated XLA ``dynamic_update_slice`` — still one
  dispatch per push, still in place.
* ``flush`` is a pointer bump: a full ring hands its buffers to the
  consumer zero-copy and restarts on fresh storage; a partial ring hands
  out one contiguous device slice per channel (two on wraparound).  No
  host staging anywhere.
* The Migrator routes **per agent group** (the fix for the seed behavior
  of shipping every flush to a single trainer): same-GPU groups forward
  directly to their co-located trainer, the rest spread least-loaded, so
  ``trainer_gmis`` balance within one flush instead of idling in turns.

Double-buffered overlap (paper §4.1)
------------------------------------
With ``overlap=True`` each ring alternates storage *generations*:
pushes stage device-resident payload references (no device work, no
donation — the producer can never stall behind a trainer still reading
the previous flush) and ``flush`` becomes a buffer *swap* instead of a
barrier — the back generation is bulk-packed in one fused dispatch
(``pack_generation``) and parked one round, while what is handed to the
trainers is the *previous* swap: arrays that had a whole serving round
of wall-clock to materialize.  Serving GMIs keep staging into the front
generation while trainer GMIs consume the back one, the
producer/consumer overlap that WarpDrive (arXiv:2108.13976) shows
end-to-end on-device RL lives or dies on.  The spill-not-drop guarantee
survives the swap: ring-overflow spills are delivered in push order,
ahead of the swap they preceded, and a final
:meth:`MultiChannelPipeline.drain` empties both generations — zero
lost, zero duplicated samples under any interleaved push/flush
schedule.

``TransferStats`` counts one transfer per channel per routed group —
physically separate moves are counted separately.  On a single-group
layout (no placement map; the Table-8 benchmark configuration) this
degenerates to exactly the seed accounting — one transfer per channel
per flush at full cross-agent size — so comparisons against the UCC
baseline (``UniChannelPipeline``, untouched, still the loser) remain
apples-to-apples; multi-GPU layouts report the real per-trainer
granularity instead.  The seed host-staging path survives as
:class:`HostStagedPipeline` for before/after benchmarking.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.channel_pack import (CHANNELS, alloc_rings,
                                        cache_payload_bytes,
                                        pack_cache_payload,
                                        pack_channels_fresh,
                                        pack_channels_xla,
                                        pack_generation,
                                        unpack_cache_payload)
from repro.rl.a3c import Experience


@dataclass
class TransferStats:
    num_transfers: int = 0
    total_bytes: int = 0
    ops: int = 0

    def record(self, tree):
        leaves = jax.tree.leaves(tree)
        self.num_transfers += 1
        self.ops += len(leaves)
        self.total_bytes += sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)

    @property
    def bytes_per_transfer(self) -> float:
        # zero transfers -> 0.0, never a ZeroDivisionError
        return self.total_bytes / max(self.num_transfers, 1)


def _payloads(exp: Experience) -> Dict[str, jax.Array]:
    return {c: getattr(exp, c) for c in CHANNELS}


# ------------------------------------------------------------- ring buffer -
class ChannelRing:
    """Preallocated per-channel device ring, one slot per push.

    ``slots`` pushes of fixed (T, N, ...) shape fit before the ring wraps
    and overwrites the oldest slot.  ``snapshot`` returns the valid slots
    oldest-first as one contiguous slice per channel (two + a concat on
    the rare wrapped read) and logically empties the ring; a full
    unwrapped ring is handed out zero-copy and the next push restarts on
    fresh storage (a single fused alloc+write dispatch).

    ``double_buffered=True`` turns ``snapshot`` into a buffer swap over
    alternating storage *generations*: pushes stage device-resident
    payload references (no device work, nothing to donate, so the
    producer can never stall behind the consumer) and the swap packs the
    whole back generation in ONE fused donation-free dispatch
    (``pack_generation``) whose output the consumer owns outright, while
    the front generation keeps staging the next round.  See
    ``kernels/channel_pack`` for the measurements that ruled out the
    shared-storage and per-push-donation alternatives.
    """

    def __init__(self, slots: int, use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 double_buffered: bool = False):
        assert slots >= 1
        self.slots = int(slots)
        self.double_buffered = bool(double_buffered)
        # on TPU the compiled pack kernel; on the CPU backend the XLA twin
        self.use_pallas = (not ops._interpret_default()) \
            if use_pallas is None else use_pallas
        self.interpret = interpret
        self.bufs: Optional[Dict[str, jax.Array]] = None
        self._staged: List[Dict[str, jax.Array]] = []   # double-buffer front
        self.head = 0          # next slot to write
        self.count = 0         # valid slots (<= slots)
        self.shape: Optional[Tuple[int, int]] = None   # (T, N)
        self._sig = None       # full per-push payload shapes

    def append(self, exp: Experience) -> None:
        pay = _payloads(exp)
        sig = tuple(tuple(pay[c].shape) for c in CHANNELS)
        if self._sig is None:
            self._sig = sig
            self.shape = pay["rewards"].shape
        elif self._sig != sig:
            raise ValueError(
                f"ring expects payload shapes {self._sig}, got {sig}")
        if self.double_buffered:
            if self.count == self.slots:   # ring semantics: evict oldest
                self._staged.pop(0)
            self._staged.append(pay)
        elif self.bufs is None:
            assert self.head == 0
            if self.use_pallas:
                self.bufs = ops.pack_channels(
                    alloc_rings(pay, self.slots), pay, jnp.int32(0),
                    interpret=self.interpret)
            else:
                self.bufs = pack_channels_fresh(pay, slots=self.slots)
        elif self.use_pallas:
            self.bufs = ops.pack_channels(self.bufs, pay,
                                          jnp.int32(self.head),
                                          interpret=self.interpret)
        else:
            self.bufs = pack_channels_xla(self.bufs, pay,
                                          jnp.int32(self.head))
        self.head = (self.head + 1) % self.slots
        self.count = min(self.count + 1, self.slots)

    # ------------------------------------------- zero-copy producer slot --
    _PRODUCED = ("obs", "actions", "rewards", "dones")

    def acquire(self, T: int, N: int, obs_dim: int, act_dim: int):
        """Hand out the ring's live producer channels plus the slot index
        for a zero-copy producer (``rl.rollout.collect_ring``): the
        megakernel rollout writes obs/action/reward/done for slot
        ``head`` directly into the returned buffers — no staged payload,
        no ``pack_channels`` re-copy.  The four arrays are DETACHED from
        the ring until :meth:`commit` reattaches them (the producer's
        jitted scan donates them).  Blocking rings only: a
        double-buffered ring's pushes already stage references, so there
        is nothing to save on its producer side."""
        if self.double_buffered:
            raise ValueError(
                "acquire/commit targets blocking rings; double-buffered "
                "rings stage payload references (use append)")
        sig = ((T, N, obs_dim), (T, N, act_dim), (T, N), (T, N), (N,), ())
        if self._sig is None:
            self._sig = sig
            self.shape = (T, N)
        elif self._sig != sig:
            raise ValueError(
                f"ring expects payload shapes {self._sig}, got {sig}")
        if self.bufs is None:
            assert self.head == 0
            S = self.slots
            self.bufs = {
                "obs": jnp.zeros((T, S * N, obs_dim), jnp.float32),
                "actions": jnp.zeros((T, S * N, act_dim), jnp.float32),
                "rewards": jnp.zeros((T, S * N), jnp.float32),
                "dones": jnp.zeros((T, S * N), jnp.float32),
                "bootstrap": jnp.zeros((S, N), jnp.float32),
                "actor_version": jnp.zeros((S, 1), jnp.int32),
            }
        out = {c: self.bufs.pop(c) for c in self._PRODUCED}
        return out, self.head

    def commit(self, bufs: Dict[str, jax.Array], bootstrap,
               actor_version) -> None:
        """Reattach the producer-written channels from :meth:`acquire`
        and finalize the slot: the bootstrap/actor_version rows land via
        two small in-place row updates, then the write pointer bumps —
        the slot becomes visible to ``snapshot`` exactly like an
        ``append``-ed push."""
        assert self.bufs is not None and self.shape is not None
        missing = [c for c in self._PRODUCED if c not in bufs]
        assert not missing, f"commit missing channels {missing}"
        self.bufs.update({c: bufs[c] for c in self._PRODUCED})
        s = self.head
        boot = jnp.asarray(bootstrap).reshape(1, -1)
        ver = jnp.asarray(actor_version, jnp.int32).reshape(1, 1)
        self.bufs["bootstrap"] = \
            self.bufs["bootstrap"].at[s:s + 1].set(boot)
        self.bufs["actor_version"] = \
            self.bufs["actor_version"].at[s:s + 1].set(ver)
        self.head = (self.head + 1) % self.slots
        self.count = min(self.count + 1, self.slots)

    def snapshot(self) -> Dict[str, jax.Array]:
        """Valid slots oldest-first as channel arrays; empties the ring.

        Double-buffered rings swap generations instead of draining in
        place: the back generation is bulk-packed in one dispatch and
        handed to the consumer; staging restarts immediately."""
        assert self.count > 0
        if self.double_buffered:
            staged, self._staged = self._staged, []
            self.head = 0
            self.count = 0
            return pack_generation(staged)

        assert self.bufs is not None
        S, (_, N) = self.slots, self.shape
        start = (self.head - self.count) % S
        bufs, count = self.bufs, self.count

        if count == S and start == 0:
            # full unwrapped ring: hand the buffers out zero-copy; the
            # next push re-allocates (consumer owns this storage now)
            self.bufs = None
            out = dict(bufs)
        else:
            def cols(buf, lo, hi):        # env-column range [lo, hi) slots
                return buf[:, lo * N:hi * N]

            def rows(buf, lo, hi):
                return buf[lo:hi]

            out = {}
            end = start + count
            for c in CHANNELS:
                take = rows if c in ("bootstrap", "actor_version") else cols
                if end <= S:
                    out[c] = take(bufs[c], start, end)
                else:                     # wrapped read: two slices
                    out[c] = jnp.concatenate(
                        [take(bufs[c], start, S), take(bufs[c], 0, end - S)],
                        axis=0 if take is rows else 1)
        self.head = 0
        self.count = 0
        out["bootstrap"] = out["bootstrap"].reshape(-1)
        out["actor_version"] = out["actor_version"].reshape(-1)
        return out



# ---------------------------------------------------------------- services -
class Dispenser:
    """Per-agent host-staged categorization (§4.2 first svc) — retained for
    the :class:`HostStagedPipeline` baseline.  In the device-resident
    pipeline the dispenser role (typed per-field split) happens inside the
    ``pack_channels`` kernel itself."""

    def __init__(self, agent_gmi: int):
        self.agent_gmi = agent_gmi
        self.out: Dict[str, List] = {c: [] for c in CHANNELS}

    def push(self, exp: Experience):
        for c in CHANNELS:
            self.out[c].append(getattr(exp, c))

    def drain(self) -> Dict[str, List]:
        out, self.out = self.out, {c: [] for c in CHANNELS}
        return out


class Compressor:
    """System-wide: batch channel payloads into large transfers.

    ``record_flush`` accounts a device-resident flush (one transfer per
    channel, sized across all groups); ``compress`` is the legacy
    host-staging path used by :class:`HostStagedPipeline`."""

    def __init__(self, min_batch: int = 1):
        self.min_batch = min_batch
        self.stats = TransferStats()

    def record_flush(self, groups: Sequence[Dict[str, jax.Array]]) -> None:
        # one transfer per channel per GROUP: groups route to different
        # trainers, so they are physically separate moves (a single-group
        # flush degenerates to the seed accounting: one per channel)
        for g in groups:
            for c in CHANNELS:
                self.stats.record(g[c])

    def compress(self, per_agent: Sequence[Dict[str, List]]) \
            -> Dict[str, jax.Array]:
        merged: Dict[str, jax.Array] = {}
        for c in CHANNELS:
            items = [x for d in per_agent for x in d[c]]
            if not items:
                continue
            arrs = [jnp.asarray(x) for x in items]
            if arrs[0].ndim == 0:
                merged[c] = jnp.stack(arrs)
            else:
                # concat along the env axis (axis 1 for (T,N,...) payloads,
                # axis 0 for (N,) bootstraps)
                axis = 1 if arrs[0].ndim >= 2 else 0
                merged[c] = jnp.concatenate(arrs, axis=axis)
            self.stats.record(merged[c])      # ONE transfer per channel
        return merged


class Migrator:
    """System-wide: route compressed channels to trainer instances."""

    def __init__(self, trainer_gmis: Sequence[int],
                 gmi_gpu: Optional[Dict[int, int]] = None):
        self.trainer_gmis = list(trainer_gmis)
        self.gmi_gpu = gmi_gpu or {}
        self.load = {t: 0 for t in self.trainer_gmis}

    def route(self, channels: Dict[str, jax.Array],
              agent_gpu: Optional[int] = None) -> int:
        """Pick the destination trainer: same-GPU direct forward if any,
        otherwise least-loaded (paper §4.2 migrator policy)."""
        same = [t for t in self.trainer_gmis
                if agent_gpu is not None
                and self.gmi_gpu.get(t) == agent_gpu]
        pool = same or self.trainer_gmis
        dst = min(pool, key=lambda t: self.load[t])
        n = channels["rewards"].shape[1] if "rewards" in channels else 1
        self.load[dst] += int(n)
        return dst


class Batcher:
    """Per-trainer: slice or stack into training batches."""

    def __init__(self, mode: str = "stack", batch_envs: Optional[int] = None):
        assert mode in ("stack", "slice")
        self.mode = mode
        self.batch_envs = batch_envs

    def prepare(self, channels: Dict[str, jax.Array]) -> List[Experience]:
        # a batch always carries ONE scalar version — the OLDEST merged
        # payload's, so downstream staleness is an upper bound for every
        # sample in the batch — whatever rank the channel arrived with
        # (0-d single push, (k,) merged pushes)
        version = jnp.min(jnp.atleast_1d(channels["actor_version"]))
        exp = Experience(
            obs=channels["obs"], actions=channels["actions"],
            rewards=channels["rewards"], dones=channels["dones"],
            bootstrap=channels["bootstrap"], actor_version=version)
        if self.mode == "stack" or self.batch_envs is None:
            return [exp]
        N = exp.rewards.shape[1]
        b = self.batch_envs
        out = []
        for s in range(0, N, b):          # ragged tail kept, never dropped
            sl = slice(s, min(s + b, N))
            out.append(Experience(
                obs=exp.obs[:, sl], actions=exp.actions[:, sl],
                rewards=exp.rewards[:, sl], dones=exp.dones[:, sl],
                bootstrap=exp.bootstrap[sl],
                actor_version=exp.actor_version))
        return out


# ---------------------------------------------------------------- pipelines -
class MultiChannelPipeline:
    """Device-resident MCC: ring-pack -> pointer-bump flush -> route ->
    batch (the paper's Dispenser/Compressor/Migrator/Batcher flow)."""

    def __init__(self, agent_gmis: Sequence[int], trainer_gmis: Sequence[int],
                 gmi_gpu: Optional[Dict[int, int]] = None,
                 batch_mode: str = "stack",
                 batch_envs: Optional[int] = None,
                 ring_slots: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 overlap: bool = False):
        self.agent_gmis = list(agent_gmis)
        # fault-injection seam (repro.fault): called once per delivering
        # group at flush time with (group_key, channels); may answer
        # "drop" (the transfer is lost in transit — the pipeline
        # RETRANSMITS it on the next flush, so the spill-not-drop
        # guarantee survives a lossy link) or "poison" (delivered
        # corrupted — the trainer-side non-finite guard must catch it)
        self.fault_hook = None
        self.dropped_flushes = 0
        self.poisoned_flushes = 0
        self.gmi_gpu = gmi_gpu or {}
        self.compressor = Compressor()
        self.migrator = Migrator(trainer_gmis, gmi_gpu)
        self.batchers = {t: Batcher(batch_mode, batch_envs)
                         for t in trainer_gmis}
        self.ring_slots = ring_slots
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.overlap = bool(overlap)
        # agents sharing a GPU share a ring (direct-forward group); agents
        # with unknown placement share the catch-all group
        self._group_of = {a: self.gmi_gpu.get(a, -1) for a in self.agent_gmis}
        self._group_size: Dict[int, int] = {}
        for g in self._group_of.values():
            self._group_size[g] = self._group_size.get(g, 0) + 1
        self._rings: Dict[Tuple[int, Tuple], ChannelRing] = {}
        # ring-overflow spill: the pipeline is lossless even when agents
        # push more often than the consumer flushes — a full ring is
        # snapshotted (still one coarse device move per channel) before
        # the overwriting push lands
        self._pending: Dict[int, List[Dict[str, jax.Array]]] = {}
        # overlap mode: the previous flush's swapped-out buffers, parked
        # one round so trainers consume round r-1 while agents serve r
        self._inflight: List[Tuple[int, Dict[str, jax.Array]]] = []
        # controller-facing counters (occupancy is read off live rings)
        self.spill_count = 0
        self.occupancy_high_water = 0.0
        self.delivered_samples = 0
        # per-round (seconds, bytes) channel-transfer timings for the
        # bandwidth calibrator; bounded so an idle consumer can't grow it
        self._transfer_samples: List[Tuple[float, int]] = []

    def _ring_for_sig(self, group: int, sig) -> ChannelRing:
        key = (group, sig)
        ring = self._rings.get(key)
        if ring is None:
            slots = self.ring_slots or self._group_size[group]
            ring = ChannelRing(slots, use_pallas=self.use_pallas,
                               interpret=self.interpret,
                               double_buffered=self.overlap)
            self._rings[key] = ring
        return ring

    def _ring_for(self, agent_gmi: int, exp: Experience) -> ChannelRing:
        sig = tuple(tuple(getattr(exp, c).shape)
                    for c in ("obs", "actions", "rewards"))
        return self._ring_for_sig(self._group_of[agent_gmi], sig)

    def push(self, agent_gmi: int, exp: Experience):
        ring = self._ring_for(agent_gmi, exp)
        if ring.count == ring.slots:       # would evict an unread slot
            group = self._group_of[agent_gmi]
            self._pending.setdefault(group, []).append(ring.snapshot())
            self.spill_count += 1
        ring.append(exp)
        self.occupancy_high_water = max(self.occupancy_high_water,
                                        ring.count / ring.slots)

    def produce(self, agent_gmi: int, T: int, N: int, obs_dim: int,
                act_dim: int, producer) -> None:
        """Zero-copy push: hand the group ring's live slot storage to the
        producer instead of packing a staged payload.

        ``producer(bufs, slot) -> (bufs, bootstrap, actor_version)``
        receives the ring's own ``{obs, actions, rewards, dones}``
        buffers (detached, donated into the producer's jitted scan) plus
        the slot index, and returns the written buffers with the
        bootstrap values and actor version for the slot — the
        ``rl.rollout.collect_ring`` contract.  Spill-not-drop and
        occupancy accounting match :meth:`push` exactly.  Blocking rings
        only (overlap mode already stages references at zero producer
        cost)."""
        if self.overlap:
            raise ValueError(
                "produce targets blocking rings; overlap mode stages "
                "payload references (push is already zero-cost on the "
                "producer side)")
        group = self._group_of[agent_gmi]
        sig = ((T, N, obs_dim), (T, N, act_dim), (T, N))
        ring = self._ring_for_sig(group, sig)
        if ring.count == ring.slots:       # would evict an unread slot
            self._pending.setdefault(group, []).append(ring.snapshot())
            self.spill_count += 1
        bufs, slot = ring.acquire(T, N, obs_dim, act_dim)
        bufs, bootstrap, version = producer(bufs, slot)
        ring.commit(bufs, bootstrap, version)
        self.occupancy_high_water = max(self.occupancy_high_water,
                                        ring.count / ring.slots)

    def flush(self) -> Dict[int, List[Experience]]:
        """Move experience toward trainer batches.

        Blocking mode (default): everything pushed since the last flush
        is snapshotted, routed, and returned — the consumer sees this
        round's data and serving implicitly waits on it.

        Overlap mode: flush is a buffer swap, not a barrier.  This
        round's pushes (spills first, in push order, then the ring swap)
        are parked in flight, and what is returned is the PREVIOUS
        flush's swap — arrays that had a whole serving round to
        materialize while pushes kept landing in the front halves.  The
        first flush returns ``{}``; :meth:`drain` delivers the tail.
        """
        t0 = time.perf_counter()
        current: List[Tuple[int, Dict[str, jax.Array]]] = []
        for gkey, snaps in self._pending.items():
            current.extend((gkey, ch) for ch in snaps)
        self._pending = {}
        for (gkey, _), ring in self._rings.items():
            if ring.count:
                current.append((gkey, ring.snapshot()))
        if self.overlap:
            groups, self._inflight = self._inflight, current
        else:
            groups = current
        if self.fault_hook is not None and groups:
            kept = []
            for gkey, ch in groups:
                action = self.fault_hook(gkey, ch)
                if action == "drop":
                    # lost in transit: back into pending for the next
                    # flush (retransmission) — lossy link, lossless data
                    self._pending.setdefault(gkey, []).append(ch)
                    self.dropped_flushes += 1
                elif action == "poison":
                    from repro.fault.inject import poison_channels
                    kept.append((gkey, poison_channels(ch)))
                    self.poisoned_flushes += 1
                else:
                    kept.append((gkey, ch))
            groups = kept
        if not groups:
            return {}
        bytes_before = self.compressor.stats.total_bytes
        self.compressor.record_flush([ch for _, ch in groups])
        out: Dict[int, List[Experience]] = {}
        for gkey, ch in groups:
            dst = self.migrator.route(
                ch, agent_gpu=None if gkey == -1 else gkey)
            out.setdefault(dst, []).extend(self.batchers[dst].prepare(ch))
            self.delivered_samples += int(np.prod(ch["rewards"].shape))
        nbytes = self.compressor.stats.total_bytes - bytes_before
        if nbytes > 0:
            # one (seconds, bytes) sample per delivering flush — the live
            # channel-transfer evidence the bandwidth calibrator consumes
            # (overlap mode undercounts: the back generation materialized
            # during the previous round, which is why the calibrator
            # down-weights transfer rows relative to reduce rows)
            self._transfer_samples.append(
                (time.perf_counter() - t0, int(nbytes)))
            del self._transfer_samples[:-64]
        return out

    def take_transfer_samples(self) -> List[Tuple[float, int]]:
        """Per-flush (seconds, bytes) channel-transfer timings since the
        last call — drained by the controller into the communicator's
        bandwidth calibrator."""
        samples, self._transfer_samples = self._transfer_samples, []
        return samples

    def requeue(self, exps: Sequence[Experience]) -> None:
        """Put consumed-but-untrained experience back into the delivery
        stream (spill-not-drop for a trainer dying mid-update): the
        batches rejoin ``_pending`` in order and re-deliver — re-routed by
        the Migrator, which no longer counts the dead trainer — at the
        next flush."""
        for exp in exps:
            self._pending.setdefault(-1, []).append(_payloads(exp))

    def drain(self) -> Dict[int, List[Experience]]:
        """Pipeline-ending flush: deliver the in-flight back buffers AND
        any still-buffered front pushes (two swap steps in overlap mode,
        one plain flush otherwise) — the overlap tail is never lost.
        Extra rounds cover retransmissions (dropped flushes re-entering
        ``_pending``), bounded so a hook that drops everything forever
        cannot livelock the drain."""
        out: Dict[int, List[Experience]] = {}
        for _ in range(2 if self.overlap else 1):
            for dst, bs in self.flush().items():
                out.setdefault(dst, []).extend(bs)
        guard = 0
        while guard < 8 and (self._pending or self._inflight
                             or any(r.count for r in self._rings.values())):
            guard += 1
            for dst, bs in self.flush().items():
                out.setdefault(dst, []).extend(bs)
        return out

    def clone_for(self, agent_gmis: Sequence[int],
                  trainer_gmis: Sequence[int],
                  gmi_gpu: Optional[Dict[int, int]] = None) \
            -> "MultiChannelPipeline":
        """A fresh pipeline over a new layout carrying THIS pipeline's
        configuration (batching, ring sizing, backend, overlap) — the
        re-plan path; counters restart with the new layout."""
        some_batcher = next(iter(self.batchers.values()), None)
        return MultiChannelPipeline(
            agent_gmis, trainer_gmis, gmi_gpu=gmi_gpu,
            batch_mode=some_batcher.mode if some_batcher else "stack",
            batch_envs=some_batcher.batch_envs if some_batcher else None,
            ring_slots=self.ring_slots, use_pallas=self.use_pallas,
            interpret=self.interpret, overlap=self.overlap)

    def ring_occupancy(self) -> float:
        """Current front-buffer fill fraction (peak across live rings)."""
        occ = [r.count / r.slots for r in self._rings.values()]
        return max(occ) if occ else 0.0

    def take_occupancy_high_water(self) -> float:
        """Peak fill fraction any ring reached since the last call.
        Exactly 1.0 once per round is the healthy interleaved pattern
        (spills, not occupancy, are the controller's overflow signal);
        ≈0 means trainers starve.  Resets the mark so each decision
        epoch sees its own peak."""
        hw, self.occupancy_high_water = self.occupancy_high_water, 0.0
        return hw

    @property
    def stats(self) -> TransferStats:
        return self.compressor.stats


class CacheChannel:
    """Point-to-point ring for prefill->decode cache migration.

    A prefill-specialist GMI finishes a prompt and ships the resulting
    cache pytree to a decode-specialist GMI's slot.  ``send`` packs the
    pytree into per-dtype contiguous buffers (``pack_cache_payload`` —
    the same coarse-grained-transfer discipline as the experience rings;
    dozens of small leaves would be the §4.2 fine-grained pathology) and
    stages the transfer; ``deliver`` moves everything staged, reassembles
    each payload bit-exactly, and records one :class:`TransferStats`
    entry plus a (seconds, bytes) timing sample per delivering batch —
    calibrator-compatible, so measured migration bandwidth feeds the same
    Table-2 fit as gradient reduces.

    Fault seam: ``fault_hook(source, item)`` may answer ``"drop"`` — the
    transfer is lost in transit and RETRANSMITTED on the next deliver
    (lossy link, lossless data, matching the experience-ring contract).
    A dead *source* is different: :meth:`fail_source` evicts that
    engine's still-staged payloads (their device buffers died with it)
    and returns the items so the caller can re-prefill them on a
    survivor — the supervisor's zero-request-loss path.
    """

    def __init__(self, name: str = "cache"):
        self.name = name
        self.fault_hook = None
        self.stats = TransferStats()
        self.dropped = 0
        self._staged: List[tuple] = []   # (source, item, bufs, meta)
        self._transfer_samples: List[Tuple[float, int]] = []

    def send(self, item, tree, *, source=None) -> int:
        """Stage ``tree`` (a cache pytree) for delivery; ``item`` is the
        caller's opaque routing handle, ``source`` identifies the sending
        engine for :meth:`fail_source`.  Returns the wire size."""
        bufs, meta = pack_cache_payload(tree)
        self._staged.append((source, item, bufs, meta))
        return cache_payload_bytes(bufs)

    @property
    def in_flight(self) -> int:
        return len(self._staged)

    def deliver(self) -> List[tuple]:
        """Deliver everything staged as ``(item, tree)`` pairs, oldest
        first.  Dropped transfers stay staged for retransmission."""
        t0 = time.perf_counter()
        staged, self._staged = self._staged, []
        out: List[tuple] = []
        nbytes = 0
        for source, item, bufs, meta in staged:
            if self.fault_hook is not None \
                    and self.fault_hook(source, item) == "drop":
                self.dropped += 1
                self._staged.append((source, item, bufs, meta))
                continue
            tree = unpack_cache_payload(bufs, meta)
            self.stats.record(tree)
            nbytes += cache_payload_bytes(bufs)
            out.append((item, tree))
        if nbytes > 0:
            self._transfer_samples.append(
                (time.perf_counter() - t0, int(nbytes)))
            del self._transfer_samples[:-64]
        return out

    def fail_source(self, source) -> List:
        """Evict payloads still staged from a dead source engine; returns
        their ``item`` handles for re-prefill on a survivor."""
        lost = [item for (src, item, _, _) in self._staged
                if src is source]
        self._staged = [e for e in self._staged if e[0] is not source]
        return lost

    def take_transfer_samples(self) -> List[Tuple[float, int]]:
        """Per-delivery (seconds, bytes) samples since the last call —
        the migration-bandwidth evidence for the calibrator."""
        samples, self._transfer_samples = self._transfer_samples, []
        return samples


class HostStagedPipeline:
    """The seed MCC: host-list staging + per-flush ``jnp.concatenate``
    re-materialization, single destination per flush.  Kept as the
    before/after baseline for ``bench_mcc`` — not for production use."""

    def __init__(self, agent_gmis: Sequence[int], trainer_gmis: Sequence[int],
                 gmi_gpu: Optional[Dict[int, int]] = None,
                 batch_mode: str = "stack",
                 batch_envs: Optional[int] = None):
        self.dispensers = {a: Dispenser(a) for a in agent_gmis}
        self.compressor = Compressor()
        self.migrator = Migrator(trainer_gmis, gmi_gpu)
        self.batchers = {t: Batcher(batch_mode, batch_envs)
                         for t in trainer_gmis}

    def push(self, agent_gmi: int, exp: Experience):
        self.dispensers[agent_gmi].push(exp)

    def flush(self) -> Dict[int, List[Experience]]:
        per_agent = [d.drain() for d in self.dispensers.values()]
        per_agent = [d for d in per_agent if any(d[c] for c in CHANNELS)]
        if not per_agent:
            return {}
        channels = self.compressor.compress(per_agent)
        dst = self.migrator.route(channels)
        return {dst: self.batchers[dst].prepare(channels)}

    def drain(self) -> Dict[int, List[Experience]]:
        """API parity with :class:`MultiChannelPipeline` (host staging has
        no in-flight buffers — drain is a plain flush)."""
        return self.flush()

    @property
    def stats(self) -> TransferStats:
        return self.compressor.stats


class UniChannelPipeline:
    """UCC baseline: every experience tuple is its own fine-grained
    transfer (one op per field per agent per round — Table 8's loser)."""

    def __init__(self, trainer_gmis: Sequence[int]):
        self.trainer_gmis = list(trainer_gmis)
        self.stats = TransferStats()
        self._rr = 0

    def send(self, exp: Experience) -> int:
        for c in CHANNELS:
            self.stats.record(getattr(exp, c))  # one transfer PER FIELD
        dst = self.trainer_gmis[self._rr % len(self.trainer_gmis)]
        self._rr += 1
        return dst
