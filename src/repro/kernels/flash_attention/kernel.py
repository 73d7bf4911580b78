"""Pallas kernel: blockwise online-softmax (flash) attention for TPU.

Tiling strategy (per grid step = one (batch·head, q-block) pair):

* the q block ``(BQ, D)`` plus the head's full K/V ``(S, D)`` live in
  VMEM — at the training shape (S=4096, D=128, bf16) that's 1 MB q + 2 MB
  K/V, comfortably inside the 16 MB v5e budget;
* the kv axis is walked in ``BK`` chunks with the standard running
  (max, denominator, accumulator) online-softmax recurrence in f32;
* causality/sliding windows skip whole chunks: the fori upper bound is
  the last visible chunk for this q block, so past-the-diagonal work is
  never issued (≈2× FLOP saving vs masked full attention);
* MXU alignment: BQ/BK multiples of the 128 lane dim; D = head_dim is
  128 on every assigned architecture.

GQA: the wrapper maps each q head to its kv head in the BlockSpec index
map — no repeat/materialization of K/V (HBM traffic stays at kv=K heads,
the GQA point).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import device

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _kernel(
    q_ref,   # (1, BQ, D)
    k_ref,   # (1, S, D)
    v_ref,   # (1, S, D)
    o_ref,   # (1, BQ, D)
    *,
    scale: float,
    causal: bool,
    window: Optional[int],
    block_k: int,
    seq_len: int,
):
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32) * scale  # (BQ, D)

    q_start = qi * bq
    row_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    if causal:
        # last kv chunk any row in this q block can see
        hi = jax.lax.div(q_start + bq - 1, block_k) + 1
    else:
        hi = seq_len // block_k
    if window is not None:
        lo = jnp.maximum(jax.lax.div(q_start - window + 1, block_k), 0)
    else:
        lo = 0

    def body(kc, carry):
        acc, m, l = carry
        k_chunk = k_ref[0, pl.dslice(kc * block_k, block_k), :].astype(jnp.float32)
        v_chunk = v_ref[0, pl.dslice(kc * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_chunk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        col_ids = kc * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1
        )
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= col_ids <= row_ids
        if window is not None:
            mask &= col_ids > row_ids - window
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=1)
        acc_new = acc * correction[:, None] + jax.lax.dot_general(
            p, v_chunk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,  # (B*H, S, D)
    k: jax.Array,  # (B*Hkv, S, D)
    v: jax.Array,  # (B*Hkv, S, D)
    *,
    group: int,  # H // Hkv — q head i reads kv head i // group
    scale: float,
    causal: bool,
    window: Optional[int],
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
) -> jax.Array:
    bh, s, d = q.shape
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    grid = (bh, s // block_q)
    return pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            window=window,
            block_k=block_k,
            seq_len=s,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # GQA mapping happens here: q head -> shared kv head
            pl.BlockSpec((1, s, d), lambda i, j, g=group: (i // g, 0, 0)),
            pl.BlockSpec((1, s, d), lambda i, j, g=group: (i // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=device.pallas_interpret(),
    )(q, k, v)
