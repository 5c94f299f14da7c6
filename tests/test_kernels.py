"""Per-kernel validation: sweep shapes/dtypes in interpret mode and
assert_allclose against the pure-jnp oracles (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.key(0)


def _qkv(B, Sq, Skv, H, KH, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KH, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KH, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", [
    (1, 128, 128, 4, 4, 32),     # MHA
    (2, 128, 128, 8, 2, 64),     # GQA 4:1
    (1, 64, 192, 4, 2, 32),      # cross lengths
    (1, 100, 100, 2, 2, 16),     # ragged (non-multiple of block)
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_attention_shapes_dtypes(shape, dtype):
    B, Sq, Skv, H, KH, hd = shape
    dt = jnp.dtype(dtype)
    q, k, v = _qkv(B, Sq, Skv, H, KH, hd, dt)
    out = ops.attention(q, k, v, causal=True, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert out.dtype == dt


@pytest.mark.parametrize("window,softcap,causal", [
    (32, None, True), (None, 25.0, True), (48, 30.0, True),
    (None, None, False),
])
def test_flash_attention_variants(window, softcap, causal):
    q, k, v = _qkv(2, 128, 128, 4, 2, 32, jnp.float32)
    out = ops.attention(q, k, v, causal=causal, window=window,
                        softcap=softcap, block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dims", [
    (60, 256, 128, 64),          # Ant trunk
    (211, 512, 512, 512, 256),   # ShadowHand trunk
    (24, 256, 128, 64),          # BallBalance trunk
])
@pytest.mark.parametrize("n", [64, 300])
def test_fused_policy_mlp(dims, n):
    ks = jax.random.split(KEY, len(dims))
    ws = [jax.random.normal(ks[i], (dims[i], dims[i + 1])) * 0.05
          for i in range(len(dims) - 1)]
    bs = [jnp.zeros((d,)) for d in dims[1:]]
    x = jax.random.normal(KEY, (n, dims[0]))
    out = ops.policy_mlp(x, ws, bs, block_n=128)
    want = ref.policy_mlp_ref(x, ws, bs)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(1, 2, 128, 16), (2, 4, 256, 32)])
@pytest.mark.parametrize("chunk", [32, 64])
def test_mlstm_kernel(shape, chunk):
    B, H, S, dh = shape
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, S, dh))
    k = jax.random.normal(ks[1], (B, H, S, dh))
    v = jax.random.normal(ks[2], (B, H, S, dh))
    li = jax.random.normal(ks[3], (B, H, S)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)
    out = ops.mlstm(q, k, v, li, lf, chunk=chunk)
    want = ref.mlstm_chunkwise_ref(q, k, v, li, lf, chunk=chunk)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("shape", [(8, 4), (12, 5), (32, 64), (7, 128)])
@pytest.mark.parametrize("gamma,lam", [(0.99, 0.95), (1.0, 1.0),
                                       (0.9, 0.5)])
def test_gae_scan_kernel(shape, gamma, lam):
    T, N = shape
    ks = jax.random.split(KEY, 4)
    rewards = jax.random.normal(ks[0], (T, N))
    values = jax.random.normal(ks[1], (T, N))
    dones = (jax.random.uniform(ks[2], (T, N)) < 0.2).astype(jnp.float32)
    last = jax.random.normal(ks[3], (N,))
    advs, rets = ops.gae_norm(rewards, values, dones, last,
                              gamma=gamma, lam=lam)
    want_a, want_r = ref.gae_norm_ref(rewards, values, dones, last,
                                      gamma, lam)
    np.testing.assert_allclose(np.asarray(advs), np.asarray(want_a),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rets), np.asarray(want_r),
                               rtol=1e-5, atol=1e-5)


def test_gae_scan_kernel_matches_unfused_gae():
    """Kernel returns == unfused rollout.gae returns; kernel advs == the
    unfused advs after global normalization."""
    from repro.rl.rollout import gae
    T, N = 16, 12
    ks = jax.random.split(KEY, 4)
    rewards = jax.random.normal(ks[0], (T, N))
    values = jax.random.normal(ks[1], (T, N))
    dones = (jax.random.uniform(ks[2], (T, N)) < 0.1).astype(jnp.float32)
    last = jax.random.normal(ks[3], (N,))
    advs_k, rets_k = ops.gae_norm(rewards, values, dones, last)
    advs_u, rets_u = gae(rewards, values, dones, last)
    np.testing.assert_allclose(np.asarray(rets_k), np.asarray(rets_u),
                               rtol=1e-5, atol=1e-5)
    want = (advs_u - advs_u.mean()) / (advs_u.std() + 1e-8)
    np.testing.assert_allclose(np.asarray(advs_k), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 4), (12, 5), (32, 64), (7, 128)])
@pytest.mark.parametrize("gamma", [0.99, 1.0, 0.5])
def test_nstep_scan_kernel(shape, gamma):
    T, N = shape
    ks = jax.random.split(KEY, 3)
    rewards = jax.random.normal(ks[0], (T, N))
    dones = (jax.random.uniform(ks[1], (T, N)) < 0.2).astype(jnp.float32)
    boot = jax.random.normal(ks[2], (N,))
    rets = ops.nstep_returns(rewards, dones, boot, gamma=gamma)
    want = ref.nstep_returns_ref(rewards, dones, boot, gamma)
    np.testing.assert_allclose(np.asarray(rets), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_nstep_scan_kernel_matches_unfused_a3c_path():
    """The fused kernel must agree with rl.a3c.nstep_returns (the unfused
    lax.scan the trainer uses when use_fused_kernels=False)."""
    from repro.rl.a3c import nstep_returns
    T, N = 16, 12
    ks = jax.random.split(KEY, 3)
    rewards = jax.random.normal(ks[0], (T, N))
    dones = (jax.random.uniform(ks[1], (T, N)) < 0.1).astype(jnp.float32)
    boot = jax.random.normal(ks[2], (N,))
    fused = nstep_returns(rewards, dones, boot, use_fused_kernels=True)
    unfused = nstep_returns(rewards, dones, boot)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("slots,pushes", [(1, 1), (3, 3), (2, 5)])
def test_channel_pack_kernel(slots, pushes):
    """Pallas pack == .at[] oracle across slot writes incl. wraparound."""
    from repro.kernels.channel_pack import (CHANNELS, alloc_rings,
                                            pack_channels)
    T, N, D, A = 6, 4, 5, 2

    def payload(i):
        k = jax.random.fold_in(KEY, i)
        return {"obs": jax.random.normal(k, (T, N, D)),
                "actions": jax.random.normal(k, (T, N, A)),
                "rewards": jax.random.normal(k, (T, N)),
                "dones": jnp.zeros((T, N)),
                "bootstrap": jnp.full((N,), float(i)),
                "actor_version": jnp.int32(i)}

    bufs_k = alloc_rings(payload(0), slots)
    bufs_r = dict(bufs_k)
    for i in range(pushes):
        slot = i % slots
        bufs_k = pack_channels(bufs_k, payload(i), jnp.int32(slot),
                               interpret=True)
        bufs_r = ref.pack_channels_ref(bufs_r, payload(i), slot)
    for c in CHANNELS:
        np.testing.assert_array_equal(np.asarray(bufs_k[c]),
                                      np.asarray(bufs_r[c]))


def test_mlstm_kernel_matches_model_block_math():
    """The kernel must agree with the model-level recurrent decode path."""
    from repro.models import ssm
    B, H, S, dh = 1, 2, 64, 16
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, S, dh))
    k = jax.random.normal(ks[1], (B, H, S, dh))
    v = jax.random.normal(ks[2], (B, H, S, dh))
    li = jax.random.normal(ks[3], (B, H, S)) * 0.5
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)
    out = ops.mlstm(q, k, v, li, lf, chunk=16)
    # step the exact recurrence
    C = jnp.zeros((B, H, dh, dh))
    n = jnp.zeros((B, H, dh))
    m = jnp.zeros((B, H))
    scale = dh ** -0.5
    outs = []
    for t in range(S):
        m_new = jnp.maximum(lf[..., t] + m, li[..., t])
        i_s = jnp.exp(li[..., t] - m_new)
        f_s = jnp.exp(lf[..., t] + m - m_new)
        C = f_s[..., None, None] * C + i_s[..., None, None] * jnp.einsum(
            "bhd,bhe->bhde", v[:, :, t], k[:, :, t])
        n = f_s[..., None] * n + i_s[..., None] * k[:, :, t]
        qt = q[:, :, t] * scale
        num = jnp.einsum("bhe,bhde->bhd", qt, C)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt, n)),
                          jnp.exp(-m_new))
        outs.append(num / den[..., None])
        m = m_new
    want = jnp.stack(outs, axis=2)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


# ------------------------------------------------------------ paged decode --
def _paged_case(B, M, page, H, KH, hd, dtype, seed=0):
    """A random but consistent paged pool: each batch row decodes at a
    random absolute position, owning shuffled physical pages for every
    virtual page at or below it (page 0 is the shared trash page)."""
    rng = np.random.default_rng(seed)
    N = B * M + 1
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k_pages = jax.random.normal(ks[1], (N, page, KH, hd), dtype)
    v_pages = jax.random.normal(ks[2], (N, page, KH, hd), dtype)
    slot_pos = np.full((N, page), -1, np.int32)
    table = np.full((B, M), -1, np.int32)
    positions = np.zeros((B,), np.int32)
    perm = iter(rng.permutation(np.arange(1, N)))
    for b in range(B):
        pos = int(rng.integers(1, M * page))
        positions[b] = pos
        for vp in range(pos // page + 1):
            pid = int(next(perm))
            table[b, vp] = pid
            hi = min(page, pos + 1 - vp * page)
            slot_pos[pid, :hi] = vp * page + np.arange(hi)
    return (q, k_pages, v_pages, jnp.asarray(slot_pos),
            jnp.asarray(table), jnp.asarray(positions))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", [None, 8])
def test_paged_attention_kernel(dtype, window):
    """Pallas gather-decode through the page table == dense ref oracle,
    full-depth and sliding-window, f32 and bf16."""
    dt = jnp.dtype(dtype)
    q, kp, vp, sp, table, pos = _paged_case(3, 4, 8, 4, 2, 32, dt, seed=5)
    out = ops.paged_attention(q, kp, vp, sp, table, pos, window=window,
                              interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, sp, table, pos, window=window)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert out.dtype == dt


def test_paged_attention_kernel_gqa_softcap():
    """GQA 4:1 heads with logit softcap, scattered unmapped pages."""
    q, kp, vp, sp, table, pos = _paged_case(2, 5, 8, 8, 2, 16,
                                            jnp.float32, seed=9)
    out = ops.paged_attention(q, kp, vp, sp, table, pos, softcap=20.0,
                              interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, sp, table, pos, softcap=20.0)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sizes", [[30, 0, 77, 50], [0, 0, 0, 0],
                                   [128, 0, 0, 128]])
def test_gmm_kernel(sizes):
    """The grouped expert kernel against its oracle on bf16-rounded
    operands (the kernel's MXU operands), forward and both gradients;
    rows past the groups read zero and take no gradient."""
    M, K, N = 256, 64, 32
    ks = jax.random.split(KEY, 3)
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    x = bf(jax.random.normal(ks[0], (M, K)))
    w = bf(jax.random.normal(ks[1], (4, K, N)))
    dy = bf(jax.random.normal(ks[2], (M, N)))
    gs = jnp.asarray(sizes, jnp.int32)
    out, vjp = jax.vjp(lambda x, w: ops.gmm(x, w, gs), x, w)
    want, vjp_ref = jax.vjp(lambda x, w: ref.gmm_ref(x, w, gs), x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(out)[sum(sizes):])
    for got, exp in zip(vjp(dy), vjp_ref(dy)):
        scale = float(jnp.abs(exp).max()) or 1.0
        # the cotangent enters the MXU as bf16 too
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(exp) / scale, atol=1e-2)
    assert not np.any(np.asarray(vjp(dy)[0])[sum(sizes):])
