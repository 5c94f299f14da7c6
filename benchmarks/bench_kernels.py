"""Pallas kernel microbenchmarks (interpret mode on CPU: numbers validate
CORRECTNESS cost only; TPU timings come from the
benchmark on the chip (bench/run.py), not this host).
Compares kernel vs pure-jnp oracle per call."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.kernels import ops, ref


def run():
    root = jax.random.key(0)

    def sub(i):
        # each draw gets its own fold_in-derived key; the root is never
        # consumed directly (prng-reuse)
        return jax.random.fold_in(root, i)

    B, S, H, KH, hd = 1, 256, 4, 2, 64
    q = jax.random.normal(sub(0), (B, S, H, hd))
    k = jax.random.normal(sub(1), (B, S, KH, hd))
    v = jax.random.normal(sub(2), (B, S, KH, hd))
    us_k = timeit(lambda: ops.attention(q, k, v, block_q=128, block_k=128))
    ref_j = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v))
    us_r = timeit(lambda: ref_j(q, k, v))
    emit("kernel_flash_attn_interp", us_k, f"ref_us={us_r:.0f}")

    dims = [211, 512, 512, 512, 256]
    ws = [jax.random.normal(sub(10 + i), (dims[i], dims[i + 1])) * 0.05
          for i in range(4)]
    bs = [jnp.zeros((d,)) for d in dims[1:]]
    x = jax.random.normal(sub(14), (512, 211))
    us_k = timeit(lambda: ops.policy_mlp(x, ws, bs))
    ref_j = jax.jit(lambda x: ref.policy_mlp_ref(x, ws, bs))
    us_r = timeit(lambda: ref_j(x))
    emit("kernel_policy_mlp_interp", us_k, f"ref_us={us_r:.0f}")

    B, H, S, dh = 1, 4, 256, 32
    qm = jax.random.normal(sub(20), (B, H, S, dh))
    li = jax.random.normal(sub(21), (B, H, S)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(sub(22), (B, H, S)) + 2.0)
    us_k = timeit(lambda: ops.mlstm(qm, qm, qm, li, lf, chunk=64))
    ref_j = jax.jit(lambda: ref.mlstm_chunkwise_ref(qm, qm, qm, li, lf,
                                                    chunk=64))
    us_r = timeit(ref_j)
    emit("kernel_mlstm_interp", us_k, f"ref_us={us_r:.0f}")

    # fused GAE + advantage normalization (PPO hot path)
    T, N = 32, 512
    ks = jax.random.split(sub(30), 4)
    rw = jax.random.normal(ks[0], (T, N))
    vl = jax.random.normal(ks[1], (T, N))
    dn = (jax.random.uniform(ks[2], (T, N)) < 0.05).astype(jnp.float32)
    lv = jax.random.normal(ks[3], (N,))
    us_k = timeit(lambda: ops.gae_norm(rw, vl, dn, lv))
    ref_j = jax.jit(lambda r, v, d, l: ref.gae_norm_ref(r, v, d, l))
    us_r = timeit(lambda: ref_j(rw, vl, dn, lv))
    emit("kernel_gae_scan_interp", us_k, f"ref_us={us_r:.0f}")

    # ring-buffer channel pack (MCC hot path): pallas vs jitted-XLA lowering
    # (both paths donate the ring, so each call gets a fresh allocation;
    # the alloc cost is identical across the two columns)
    from repro.kernels import channel_pack as cp
    pay = {"obs": jax.random.normal(sub(40), (T, 64, 48)),
           "actions": jax.random.normal(sub(41), (T, 64, 12)),
           "rewards": jax.random.normal(sub(42), (T, 64)),
           "dones": jnp.zeros((T, 64)),
           "bootstrap": jnp.zeros((64,)),
           "actor_version": jnp.int32(0)}
    slot = jnp.int32(1)
    us_k = timeit(
        lambda: ops.pack_channels(cp.alloc_rings(pay, 4), pay, slot))
    us_x = timeit(
        lambda: cp.pack_channels_xla(cp.alloc_rings(pay, 4), pay, slot))
    emit("kernel_channel_pack_interp", us_k, f"xla_us={us_x:.0f}")
