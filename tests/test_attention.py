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
