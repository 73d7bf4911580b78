"""flash_attention + decode_attention Pallas kernels vs jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.flash_attention import attention_ref, flash_attention


def qkv(rng, b, h, hkv, s, d, dtype=np.float32):
    q = rng.standard_normal((b, h, s, d)).astype(dtype)
    k = rng.standard_normal((b, hkv, s, d)).astype(dtype)
    v = rng.standard_normal((b, hkv, s, d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


TOL = dict(rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize(
    "b,h,hkv,s,d",
    [
        (1, 2, 2, 128, 32),   # MHA
        (1, 4, 2, 128, 32),   # GQA 2:1
        (2, 4, 1, 256, 64),   # MQA
        (1, 2, 2, 192, 32),   # seq not multiple of default blocks
    ],
)
def test_flash_causal_shapes(b, h, hkv, s, d, rng):
    q, k, v = qkv(rng, b, h, hkv, s, d)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    exp = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), **TOL)


def test_flash_noncausal(rng):
    q, k, v = qkv(rng, 1, 2, 2, 128, 32)
    got = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    exp = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), **TOL)


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_sliding_window(window, rng):
    q, k, v = qkv(rng, 1, 2, 1, 256, 32)
    got = flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=64
    )
    exp = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), **TOL)


def test_flash_bf16(rng):
    q, k, v = qkv(rng, 1, 2, 2, 128, 32, dtype=np.float32)
    q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    exp = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32), rtol=3e-2, atol=3e-2
    )


def test_flash_block_shape_independence(rng):
    """Block size must not change the math."""
    q, k, v = qkv(rng, 1, 2, 2, 256, 32)
    a = flash_attention(q, k, v, block_q=64, block_k=64)
    b = flash_attention(q, k, v, block_q=128, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------- decode
@pytest.mark.parametrize(
    "b,h,hkv,s,d",
    [
        (1, 2, 2, 256, 32),
        (2, 4, 2, 512, 64),
        (3, 4, 1, 384, 32),
    ],
)
def test_decode_shapes(b, h, hkv, s, d, rng):
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, s + 1, b).astype(np.int32))
    got = decode_attention(q, k, v, lengths, block_s=128)
    exp = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), **TOL)


def test_decode_full_cache(rng):
    b, h, hkv, s, d = 2, 2, 2, 256, 32
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    lengths = jnp.full((b,), s, jnp.int32)
    got = decode_attention(q, k, v, lengths, block_s=64)
    exp = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), **TOL)


def test_decode_tiny_length(rng):
    """Only the first cache entry is valid — masking must be exact."""
    b, h, hkv, s, d = 1, 2, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)).astype(np.float32))
    lengths = jnp.ones((b,), jnp.int32)
    got = decode_attention(q, k, v, lengths, block_s=64)
    exp = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), **TOL)
    # attending to 1 token == that token's value
    np.testing.assert_allclose(
        np.asarray(got[0, 0]), np.asarray(v[0, 0, 0]), **TOL
    )


def test_decode_bf16(rng):
    b, h, hkv, s, d = 2, 4, 2, 256, 32
    q = jnp.asarray(rng.standard_normal((b, h, d))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d))).astype(jnp.bfloat16)
    lengths = jnp.full((b,), s, jnp.int32)
    got = decode_attention(q, k, v, lengths, block_s=128)
    exp = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(exp, np.float32), rtol=3e-2, atol=3e-2
    )
