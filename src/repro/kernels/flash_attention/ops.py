"""Public jit'd wrapper for the flash attention kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention_kernel,
)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k"),
)
def flash_attention(
    q: jax.Array,  # (B, H, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    b, h, s, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0, f"GQA needs H({h}) % Hkv({hkv}) == 0"
    group = h // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    bq = min(block_q, s)
    bk = min(block_k, s)
    out = flash_attention_kernel(
        q.reshape(b * h, s, d),
        k.reshape(b * hkv, s, d),
        v.reshape(b * hkv, s, d),
        group=group,
        scale=scale,
        causal=causal,
        window=window,
        block_q=bq,
        block_k=bk,
    )
    return out.reshape(b, h, s, d)
