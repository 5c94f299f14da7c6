"""Equivalence tests for the §Perf optimizations: they must change the
schedule/layout, never the math."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.models import transformer as T


def test_decode_unroll_matches_scan():
    cfg = get_reduced("qwen2-72b")
    key = jax.random.key(1)
    B, S = 2, 16
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    params = T.init_model(key, cfg)
    P_len = S - 3
    lg, c_scan = T.prefill(params, cfg, {"tokens": toks[:, :P_len]},
                           max_seq=S)
    c_unroll = jax.tree.map(lambda x: x, c_scan)
    for i in range(3):
        pos = jnp.full((B,), P_len + i, jnp.int32)
        l_s, c_scan = T.decode_step(params, cfg, toks[:, P_len + i], pos,
                                    c_scan, unroll=False)
        l_u, c_unroll = T.decode_step(params, cfg, toks[:, P_len + i], pos,
                                      c_unroll, unroll=True)
        np.testing.assert_allclose(np.asarray(l_s), np.asarray(l_u),
                                   rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.tree.leaves(c_scan), jax.tree.leaves(c_unroll)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_per_layer_ring_cache_equivalence():
    """gemma2-style local/global: per-layer ring caches (local layers hold
    only their window) must reproduce full-forward logits exactly."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(name="g", num_layers=4, d_model=64, num_heads=4,
                      num_kv_heads=2, d_ff=128, vocab_size=64,
                      local_global=True, sliding_window=8,
                      attn_softcap=50.0, final_softcap=30.0)
    key = jax.random.key(3)
    B, S = 2, 24
    toks = jax.random.randint(key, (B, S), 0, 64)
    params = T.init_model(key, cfg)
    full, _ = T.forward(params, cfg, {"tokens": toks})
    P_len = S - 5
    lg, caches = T.prefill(params, cfg, {"tokens": toks[:, :P_len]},
                           max_seq=S, per_layer_cache=True)
    assert isinstance(caches, list)
    assert [c.k.shape[1] for c in caches] == [8, 24, 8, 24]
    for i in range(5):
        pos = jnp.full((B,), P_len + i, jnp.int32)
        lg, caches = T.decode_step(params, cfg, toks[:, P_len + i], pos,
                                   caches, unroll=True)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, P_len + i]),
                                   rtol=4e-4, atol=4e-4)
