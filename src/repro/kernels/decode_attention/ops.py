"""Public jit'd wrapper for decode attention (GQA + ragged lengths)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    DEFAULT_BLOCK_S,
    decode_attention_kernel,
)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s")
)
def decode_attention(
    q: jax.Array,        # (B, H, D)
    k_cache: jax.Array,  # (B, Hkv, S, D)
    v_cache: jax.Array,  # (B, Hkv, S, D)
    lengths: jax.Array,  # (B,) int32
    *,
    scale: Optional[float] = None,
    block_s: int = DEFAULT_BLOCK_S,
) -> jax.Array:
    b, h, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    assert h % hkv == 0
    group = h // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    bs = min(block_s, s)
    lengths_bh = jnp.broadcast_to(lengths[:, None], (b, h)).reshape(b * h)
    out = decode_attention_kernel(
        q.reshape(b * h, 1, d),
        k_cache.reshape(b * hkv, s, d),
        v_cache.reshape(b * hkv, s, d),
        lengths_bh.astype(jnp.int32),
        group=group,
        scale=scale,
        block_s=bs,
    )
    return out.reshape(b, h, d).astype(q.dtype)
