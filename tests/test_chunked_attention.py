"""Chunked online-softmax ("flash in XLA") vs dense reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.attention import _sdpa, _sdpa_chunked


def qkv(rng, b, h, hkv, s, d):
    return (
        jnp.asarray(rng.standard_normal((b, h, s, d)).astype(np.float32)),
        jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32)),
        jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32)),
    )


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_chunked_equals_dense_causal(chunk, rng):
    q, k, v = qkv(rng, 2, 4, 2, 256, 16)
    dense = _sdpa(q, k, v, causal=True, window=None)
    chunked = _sdpa_chunked(q, k, v, causal=True, window=None, chunk=chunk)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), rtol=2e-3, atol=2e-3)


def test_chunked_with_window(rng):
    q, k, v = qkv(rng, 1, 2, 1, 192, 16)
    dense = _sdpa(q, k, v, causal=True, window=50)
    chunked = _sdpa_chunked(q, k, v, causal=True, window=50, chunk=64)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), rtol=2e-3, atol=2e-3)


def test_chunked_pads_non_divisible(rng):
    q, k, v = qkv(rng, 1, 2, 2, 100, 16)  # 100 % 64 != 0 -> padded
    dense = _sdpa(q, k, v, causal=True, window=None)
    chunked = _sdpa_chunked(q, k, v, causal=True, window=None, chunk=64)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), rtol=2e-3, atol=2e-3)


def test_mla_chunked_equals_dense(rng):
    import dataclasses

    from repro.models.mla import MLAConfig, init_mla, mla_train

    cfg_dense = MLAConfig(
        d_model=64, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, chunk=None,
        compute_dtype=jnp.float32,
    )
    cfg_chunk = dataclasses.replace(cfg_dense, chunk=32)
    p = init_mla(jax.random.PRNGKey(0), cfg_dense)
    x = jnp.asarray(rng.standard_normal((2, 96, 64)).astype(np.float32))
    pos = jnp.arange(96)
    a = mla_train(p, cfg_dense, x, pos)
    b = mla_train(p, cfg_chunk, x, pos)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-3)


@given(
    s=st.integers(16, 200),
    chunk=st.sampled_from([16, 32, 64]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=10, deadline=None)
def test_property_chunked_matches_dense(s, chunk, seed):
    rng = np.random.default_rng(seed)
    q, k, v = qkv(rng, 1, 2, 2, s, 8)
    dense = _sdpa(q, k, v, causal=True, window=None)
    chunked = _sdpa_chunked(q, k, v, causal=True, window=None, chunk=chunk)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(dense), rtol=5e-3, atol=5e-3)
