import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (_chunked_attention, _direct_attention,
                                    attention, init_attention_params,
                                    make_cache)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 16])
def test_chunked_matches_direct(causal, window):
    key = jax.random.key(0)
    B, S, H, KH, hd = 2, 64, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.key(1), (B, S, KH, hd))
    v = jax.random.normal(jax.random.key(2), (B, S, KH, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o1 = _direct_attention(q, k, v, pos, pos, causal, window, None, hd**-0.5)
    o2 = _chunked_attention(q, k, v, pos, pos, causal, window, None,
                            hd**-0.5, q_block=16, kv_block=16)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


def test_chunked_with_softcap_and_ragged_blocks():
    key = jax.random.key(3)
    B, S, H, hd = 1, 50, 2, 8      # 50 does not divide the block size
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.key(4), (B, S, H, hd))
    v = jax.random.normal(jax.random.key(5), (B, S, H, hd))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o1 = _direct_attention(q, k, v, pos, pos, True, None, 25.0, hd**-0.5)
    o2 = _chunked_attention(q, k, v, pos, pos, True, None, 25.0, hd**-0.5,
                            q_block=16, kv_block=16)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dv", [8, 24])
def test_chunked_matches_direct_value_width(dv):
    """Values narrower or wider than queries and keys (latent attention:
    192-wide queries and keys, 128-wide values); causal, ragged blocks."""
    B, S, H, KH, hd = 2, 40, 4, 4, 16
    q = jax.random.normal(jax.random.key(7), (B, S, H, hd))
    k = jax.random.normal(jax.random.key(8), (B, S, KH, hd))
    v = jax.random.normal(jax.random.key(9), (B, S, KH, dv))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    o1 = _direct_attention(q, k, v, pos, pos, True, None, None, hd**-0.5)
    o2 = _chunked_attention(q, k, v, pos, pos, True, None, None, hd**-0.5,
                            q_block=16, kv_block=16)
    assert o1.shape == o2.shape == (B, S, H, dv)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda v: jnp.sum(_direct_attention(
        q, k, v, pos, pos, True, None, None, hd**-0.5) ** 2))(v)
    g2 = jax.grad(lambda v: jnp.sum(_chunked_attention(
        q, k, v, pos, pos, True, None, None, hd**-0.5, q_block=16,
        kv_block=16) ** 2))(v)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)


def test_ring_cache_decode_matches_full_cache():
    """Sliding-window decode via ring buffer == full cache + window mask."""
    key = jax.random.key(6)
    D, H, KH, hd, W = 32, 4, 2, 8, 8
    p = init_attention_params(key, D, H, KH, hd)
    B, S = 2, 24
    xs = jax.random.normal(key, (B, S, D))
    ring = make_cache(B, S, KH, hd, window=W)
    full = make_cache(B, S, KH, hd, window=None)
    assert ring.k.shape[1] == W and full.k.shape[1] == S
    for t in range(S):
        pos = jnp.full((B, 1), t, jnp.int32)
        o_r, ring = attention(p, xs[:, t:t+1], num_heads=H, num_kv_heads=KH,
                              head_dim=hd, positions=pos, window=W,
                              cache=ring)
        o_f, full = attention(p, xs[:, t:t+1], num_heads=H, num_kv_heads=KH,
                              head_dim=hd, positions=pos, window=W,
                              cache=full)
        np.testing.assert_allclose(o_r, o_f, rtol=1e-5, atol=1e-5)


def test_prefill_writes_tail_into_ring():
    key = jax.random.key(7)
    D, H, KH, hd, W = 16, 2, 2, 8, 4
    p = init_attention_params(key, D, H, KH, hd)
    B, S = 1, 10
    x = jax.random.normal(key, (B, S, D))
    cache = make_cache(B, S, KH, hd, window=W)
    pos = jnp.arange(S)[None]
    _, cache = attention(p, x, num_heads=H, num_kv_heads=KH, head_dim=hd,
                         positions=pos, window=W, cache=cache)
    # slots must hold the last W absolute positions
    assert sorted(np.asarray(cache.slot_pos[0]).tolist()) == [6, 7, 8, 9]


@pytest.mark.parametrize("blocks,want", [(None, (240, 1024)),
                                         ((1024, 1024), (70, 256))],
                         ids=["moonlight_cell", "moonlight_cell_1024_blocks"])
def test_mla_block_counts(blocks, want):
    """The attention blocks the kernel computes and those of the full
    squares, from the lengths alone: the Moonlight cell's rows (a
    512-token prompt and responses of 1536/3072/5120/7680) compute 240 of
    1024 blocks of ``mla``'s 512 x 512 per layer and pass, 70 of 256 at
    1024 x 1024."""
    from repro.kernels.flash_attention import block_counts
    from repro.models.attention import MLA_BLOCKS
    if blocks is None:
        assert MLA_BLOCKS == (512, 512)
        blocks = MLA_BLOCKS
    got = block_counts(jnp.array([2048, 3584, 5632, 8192]), 8192, 8192,
                       block_q=blocks[0], block_k=blocks[1])
    assert tuple(int(x) for x in got) == want


@pytest.mark.parametrize("seq,threshold,want", [
    (48, 16, (6 + 3, 2 * 9)), (24, 4096, (2 * 4, 2 * 4))],
    ids=["kernel_path", "direct_path"])
def test_mla_counts_the_blocks_of_the_path_it_ran(seq, threshold, want,
                                                  monkeypatch):
    """``mla_with_blocks`` counts the 16 x 16 blocks of the path it took,
    on rows of ``seq`` and 20 real positions: the kernel's blocks holding
    a causal pair inside a row's length, or the direct path's whole
    squares."""
    import repro.models.attention as A
    monkeypatch.setattr(A, "MLA_BLOCKS", (16, 16))
    x = jax.random.normal(jax.random.key(12), (2, seq, 32))
    _, counts = jax.jit(lambda p, x: A.mla_with_blocks(
        p, x, jnp.arange(seq), num_heads=4, qk_nope_dim=16, qk_rope_dim=8,
        v_dim=12, rope_theta=10_000.0, norm_eps=1e-5,
        chunked_threshold=threshold, lengths=jnp.array([seq, 20])))(
            _tiny_mla(jax.random.key(11)), x)
    assert (int(counts["attn_blocks"]), int(counts["attn_blocks_full"])) \
        == want


def _tiny_mla(key, D=32, H=4, dn=16, dr=8, dv=12, r=20):
    ks = jax.random.split(key, 4)

    def w(k, i, o):
        return {"w": jax.random.normal(k, (i, o)) * i ** -0.5}
    return {"wq": w(ks[0], D, H * (dn + dr)), "wkv_a": w(ks[1], D, r + dr),
            "kv_norm": {"scale": jnp.ones((r,))},
            "wkv_b": w(ks[2], r, H * (dn + dv)),
            "wo": w(ks[3], H * dv, D)}


@pytest.mark.parametrize("lengths", [[48, 48], [48, 19]],
                         ids=["whole_rows", "right_padded"])
def test_mla_kernel_path_matches_chunked_scan(lengths, monkeypatch):
    """``mla`` on the flash kernel against ``mla`` on the XLA chunked scan
    it replaced, for a tiny latent config: the outputs of real rows (the
    kernel's padding rows are zero) and the parameter gradients of a loss
    over the real rows."""
    from repro.kernels import ops
    from repro.models.attention import mla
    B, S, D = 2, 48, 32
    params = _tiny_mla(jax.random.key(11))
    x = jax.random.normal(jax.random.key(12), (B, S, D))
    w = jax.random.normal(jax.random.key(13), (B, S, D))
    L = jnp.array(lengths)
    real = (jnp.arange(S)[None] < L[:, None])[..., None]
    kw = dict(num_heads=4, qk_nope_dim=16, qk_rope_dim=8, v_dim=12,
              rope_theta=10_000.0, norm_eps=1e-5, chunked_threshold=16)

    def run(p):
        return mla(p, x, jnp.arange(S), lengths=L, **kw)

    def loss(p):
        return jnp.sum(jnp.where(real, run(p), 0.0) * w)

    import repro.models.attention as A
    monkeypatch.setattr(A, "MLA_BLOCKS", (16, 8))
    out, grads = jax.jit(run)(params), jax.jit(jax.grad(loss))(params)

    pos = jnp.arange(S)[None]
    monkeypatch.setattr(ops, "attention", lambda q, k, v, lengths, **_:
                        _chunked_attention(
                            q, k, v, pos, pos, True, None, None,
                            (16 + 8) ** -0.5, q_block=16, kv_block=16))
    want, want_g = jax.jit(run)(params), jax.jit(jax.grad(loss))(params)

    np.testing.assert_allclose(jnp.where(real, out, 0.0),
                               jnp.where(real, want, 0.0),
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(out)[1, lengths[1]:] == 0)
    jax.tree.map(lambda g, gw: np.testing.assert_allclose(
        g, gw, rtol=1e-4, atol=1e-5), grads, want_g)


def test_grpo_step_counts_attention_blocks(monkeypatch):
    """``train_step``'s ``attn_blocks`` / ``attn_blocks_full``: every
    layer's block pairs of one pass, summed over the minibatches.  A tiny
    latent config on rows of 48 and 20 real positions, one row a minibatch,
    its rows on the kernel path at 16 x 16 blocks: 6 + 3 of 9 + 9 blocks
    a layer (the dense layer's and the MoE layers')."""
    import functools

    import repro.models.attention as A
    from repro.configs.moonlight_16b_a3b import reduced
    from repro.models.transformer import init_latent_moe
    from repro.optim import adam_init
    from repro.rl.grpo import GRPOConfig, TokenBatch, train_step
    monkeypatch.setattr(A, "MLA_BLOCKS", (16, 16))
    monkeypatch.setattr(A, "mla_with_blocks", functools.partial(
        A.mla_with_blocks, chunked_threshold=16))
    cfg = reduced()
    S, lengths = 48, jnp.array([48, 20])
    valid = jnp.arange(S)[None] < lengths[:, None]
    tokens = jax.random.randint(jax.random.key(3), (2, S), 0, cfg.vocab_size)
    batch = TokenBatch(tokens, valid, valid[:, 1:].astype(jnp.float32),
                       jnp.array([1.0, -1.0]))
    params = init_latent_moe(jax.random.key(4), cfg)
    _, _, metrics = jax.jit(lambda p, o, b: train_step(
        p, o, b, GRPOConfig(num_minibatches=2), cfg))(
            params, adam_init(params), batch)
    assert int(metrics["attn_blocks"]) == cfg.num_layers * (6 + 3)
    assert int(metrics["attn_blocks_full"]) == cfg.num_layers * (9 + 9)
