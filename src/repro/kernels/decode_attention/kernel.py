"""Pallas kernel: single-token decode attention against a long KV cache.

Decode is memory-bound: each step must stream the whole KV cache from HBM
once, and arithmetic intensity is O(1).  The tiling therefore optimizes
for streaming, not reuse:

* grid = (batch·heads, S/BS): the cache axis is *grid-blocked* — unlike
  prefill, a 500k-token cache (128 GB global, ~8 MB per head-block slice)
  must never sit in VMEM at once; each step touches one ``(BS, D)`` chunk;
* the online-softmax numerator (1,D) lives in the revisited output
  block, and the running max and denominator (1,1) in VMEM scratch — the
  TPU sequential grid makes the recurrence exact;
* the final grid step for each (b,h) normalizes numerator/denominator
  in-place, so no extra pass over the output is needed;
* cache entries past ``length`` (ragged batches) are masked by comparing
  the chunk's global positions against the per-sequence length, which is
  scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import device

DEFAULT_BLOCK_S = 1024
_NEG_INF = -1e30


def _kernel(
    len_ref,   # (B*H,) int32 in SMEM — valid length per sequence
    q_ref,     # (1, 1, D)
    k_ref,     # (1, BS, D)
    v_ref,     # (1, BS, D)
    o_ref,     # (1, 1, D)  — numerator accumulator, normalized at the end
    m_ref,     # (1, 1) f32 VMEM scratch — running max
    l_ref,     # (1, 1) f32 VMEM scratch — running denominator
    *,
    scale: float,
    block_s: int,
    num_s_blocks: int,
):
    sc = pl.program_id(1)

    @pl.when(sc == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # (1, D)
    k = k_ref[0].astype(jnp.float32)                # (BS, D)
    v = v_ref[0].astype(jnp.float32)                # (BS, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (1, BS)
    pos = sc * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    s = jnp.where(pos < len_ref[pl.program_id(0)], s, _NEG_INF)

    m_prev = m_ref[...]                              # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                           # (1, BS)
    correction = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * correction + jnp.sum(p, axis=1, keepdims=True)
    acc = o_ref[0].astype(jnp.float32) * correction + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    # numerator stays f32 across chunks (o_ref dtype is f32 by contract);
    # the wrapper casts the final normalized value back to q.dtype
    @pl.when(sc == num_s_blocks - 1)
    def _finalize():
        o_ref[0] = acc / jnp.maximum(l_new, 1e-30)

    @pl.when(sc != num_s_blocks - 1)
    def _stash():
        o_ref[0] = acc


def decode_attention_kernel(
    q: jax.Array,        # (B*H, 1, D)
    k_cache: jax.Array,  # (B*Hkv, S, D)
    v_cache: jax.Array,  # (B*Hkv, S, D)
    lengths: jax.Array,  # (B*H,) int32 (pre-broadcast per q head)
    *,
    group: int,
    scale: float,
    block_s: int = DEFAULT_BLOCK_S,
) -> jax.Array:
    bh, _, d = q.shape
    s = k_cache.shape[1]
    assert s % block_s == 0, (s, block_s)
    num_s_blocks = s // block_s
    kv_block = pl.BlockSpec(
        (1, block_s, d), lambda i, j, lens, g=group: (i // g, j, 0)
    )
    head_block = pl.BlockSpec((1, 1, d), lambda i, j, lens: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_s=block_s, num_s_blocks=num_s_blocks
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, num_s_blocks),
            in_specs=[head_block, kv_block, kv_block],
            out_specs=head_block,
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), jnp.float32),
        interpret=device.pallas_interpret(),
    )(lengths, q, k_cache, v_cache)
