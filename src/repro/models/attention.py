"""GQA/MQA/MHA attention with RoPE, optional qk-norm and sliding window.

Three entry points matching the three workload shapes:
* ``attend_train``   — full-sequence causal (training / prefill), pure-jnp
  reference math by default, Pallas flash kernel when enabled;
* ``prefill``        — causal pass that also returns the KV cache;
* ``decode_step``    — one token against a KV cache (serving), pure-jnp
  masked softmax by default, Pallas decode kernel when enabled.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (
    Params,
    apply_rope,
    init_linear,
    init_rmsnorm,
    linear,
    rmsnorm,
)


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    rope_theta: float = 10000.0
    qk_norm: bool = False
    window: Optional[int] = None  # sliding-window size (SWA archs)
    use_flash_kernel: bool = False  # Pallas path (TPU target)
    #: kv-chunked online-softmax ("flash in XLA"): bounds the scores
    #: working set to S×chunk instead of S×S. None = dense S×S scores.
    chunk: Optional[int] = 1024
    compute_dtype: Any = jnp.bfloat16


def init_attention(key, cfg: AttentionConfig, *, dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    p: Params = {
        "wq": init_linear(kq, cfg.d_model, cfg.n_heads * cfg.d_head, dtype=dtype),
        "wk": init_linear(kk, cfg.d_model, cfg.n_kv_heads * cfg.d_head, dtype=dtype),
        "wv": init_linear(kv, cfg.d_model, cfg.n_kv_heads * cfg.d_head, dtype=dtype),
        "wo": init_linear(
            ko, cfg.n_heads * cfg.d_head, cfg.d_model, dtype=dtype,
            scale=(cfg.n_heads * cfg.d_head) ** -0.5,
        ),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(cfg.d_head, dtype=dtype)
        p["k_norm"] = init_rmsnorm(cfg.d_head, dtype=dtype)
    return p


def _project_qkv(
    p: Params, cfg: AttentionConfig, x: jax.Array, positions: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    cd = cfg.compute_dtype
    q = linear(p["wq"], x, compute_dtype=cd).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = linear(p["wk"], x, compute_dtype=cd).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = linear(p["wv"], x, compute_dtype=cd).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    q = apply_rope(q.swapaxes(1, 2), positions, theta=cfg.rope_theta)  # (B,H,S,D)
    k = apply_rope(k.swapaxes(1, 2), positions, theta=cfg.rope_theta)
    v = v.swapaxes(1, 2)
    return q, k, v


def _sdpa(
    q: jax.Array,  # (B,H,S,D)
    k: jax.Array,  # (B,Hkv,T,D)
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    q_offset: jax.Array | int = 0,
) -> jax.Array:
    """Reference scaled-dot-product attention with GQA head grouping."""
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = h // hkv
    qg = q.reshape(b, hkv, group, s, d).astype(jnp.float32)
    scores = jnp.einsum("bkgqd,bktd->bkgqt", qg, k.astype(jnp.float32))
    scores = scores * (d**-0.5)
    rows = q_offset + jnp.arange(s)[:, None]
    cols = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,bktd->bkgqd", w, v.astype(jnp.float32))
    return out.reshape(b, h, s, d)


_NEG = -1e30


def _sdpa_chunked(
    q: jax.Array,  # (B,H,S,D)
    k: jax.Array,  # (B,Hkv,T,D)
    v: jax.Array,
    *,
    causal: bool,
    window: Optional[int],
    chunk: int,
    q_offset: jax.Array | int = 0,
) -> jax.Array:
    """kv-chunked online-softmax attention ("flash" expressed in XLA).

    A ``lax.scan`` over key/value chunks with running (max, denominator,
    accumulator) carries — the scores working set is S×chunk, so 32k/500k
    prefill shapes stop owning the memory roofline.  Numerically matches
    ``_sdpa`` to f32 rounding (same online recurrence as the Pallas
    kernel; cross-checked in tests).
    """
    from repro.distribution.sharding import constrain_heads

    q = constrain_heads(q)  # heads over TP (q heads always divide)
    k = constrain_heads(k)  # kv heads shard only when they divide TP
    v = constrain_heads(v)
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = h // hkv
    cd = q.dtype
    pad = -t % chunk
    if pad:
        # padded keys sit at positions >= t > any causal row — masked for
        # free by the causal comparison (train paths are always causal)
        assert causal, "chunk padding relies on causal masking"
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        t += pad
    qg = (
        q.reshape(b, hkv, group, s, d).astype(jnp.float32) * (d**-0.5)
    ).astype(cd)
    rows = q_offset + jnp.arange(s)  # (S,)

    def body(carry, kc):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(k, kc * chunk, chunk, 2)
        vs = jax.lax.dynamic_slice_in_dim(v, kc * chunk, chunk, 2)
        scores = jnp.einsum(
            "bkgqd,bktd->bkgqt", qg, ks, preferred_element_type=jnp.float32
        )  # (B,Hkv,G,S,c)
        cols = kc * chunk + jnp.arange(chunk)
        mask = jnp.ones((s, chunk), bool)
        if causal:
            mask &= cols[None, :] <= rows[:, None]
        if window is not None:
            mask &= cols[None, :] > rows[:, None] - window
        scores = jnp.where(mask[None, None, None], scores, _NEG)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqt,bktd->bkgqd", p.astype(cd), vs,
            preferred_element_type=jnp.float32,
        )
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hkv, group, s, d), jnp.float32)
    m0 = jnp.full((b, hkv, group, s), _NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, s), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(t // chunk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, s, d)


def _attend_full(q, k, v, cfg: AttentionConfig):
    """Dispatch dense vs chunked by config and shape."""
    t = k.shape[2]
    if cfg.chunk is not None and t > cfg.chunk:
        return _sdpa_chunked(
            q, k, v, causal=True, window=cfg.window, chunk=cfg.chunk
        )
    return _sdpa(q, k, v, causal=True, window=cfg.window)


def attend_train(
    p: Params, cfg: AttentionConfig, x: jax.Array, positions: jax.Array
) -> jax.Array:
    """Causal self-attention over the full sequence."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    if cfg.use_flash_kernel:
        from repro.kernels.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=True, window=cfg.window)
    else:
        out = _attend_full(q, k, v, cfg)
    b, h, s, d = out.shape
    merged = out.swapaxes(1, 2).reshape(b, s, h * d).astype(cfg.compute_dtype)
    return linear(p["wo"], merged, compute_dtype=cfg.compute_dtype)


# ------------------------------------------------------------------ serving
def init_cache(
    cfg: AttentionConfig, batch: int, max_len: int, *, dtype=jnp.bfloat16
) -> Dict[str, jax.Array]:
    shape = (batch, cfg.n_kv_heads, max_len, cfg.d_head)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def prefill(
    p: Params,
    cfg: AttentionConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    q, k, v = _project_qkv(p, cfg, x, positions)
    s = x.shape[1]
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
        ),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
        ),
    }
    if cfg.use_flash_kernel:
        from repro.kernels.flash_attention import flash_attention

        out = flash_attention(q, k, v, causal=True, window=cfg.window)
    else:
        out = _attend_full(q, k, v, cfg)
    b, h, _, d = out.shape
    merged = out.swapaxes(1, 2).reshape(b, s, h * d).astype(cfg.compute_dtype)
    return linear(p["wo"], merged, compute_dtype=cfg.compute_dtype), cache


def decode_step(
    p: Params,
    cfg: AttentionConfig,
    x: jax.Array,           # (B, 1, d_model)
    cache: Dict[str, jax.Array],
    lengths: jax.Array,     # (B,) — tokens already in the cache
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b = x.shape[0]
    cd = cfg.compute_dtype
    positions = lengths[:, None]  # this token's position (B, 1)
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)
    # append the new kv at each sequence's own length (ragged batch)
    s_max = cache["k"].shape[2]
    onehot = (
        jnp.arange(s_max)[None, :] == lengths[:, None]
    ).astype(cache["k"].dtype)  # (B, S)
    oh = onehot[:, None, :, None]
    # REPLACE semantics (not add): re-writing a slot position must be
    # idempotent so serving can reuse slots safely
    k_cache = cache["k"] * (1 - oh) + oh * k_new.astype(cache["k"].dtype)
    v_cache = cache["v"] * (1 - oh) + oh * v_new.astype(cache["v"].dtype)
    new_lengths = lengths + 1
    if cfg.use_flash_kernel:
        from repro.kernels.decode_attention import decode_attention

        out = decode_attention(
            q[:, :, 0], k_cache, v_cache, new_lengths
        )  # (B, H, D)
        out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
    else:
        t = jnp.arange(s_max)[None, :]
        visible = t < new_lengths[:, None]
        if cfg.window is not None:
            visible &= t > (new_lengths[:, None] - 1 - cfg.window)
        scores = jnp.einsum(
            "bkgqd,bktd->bkgqt",
            q.reshape(b, cfg.n_kv_heads, -1, 1, cfg.d_head).astype(jnp.float32),
            k_cache.astype(jnp.float32),
        ) * (cfg.d_head**-0.5)
        scores = jnp.where(visible[:, None, None, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqt,bktd->bkgqd", w, v_cache.astype(jnp.float32))
        out = out.reshape(b, cfg.n_heads, 1, cfg.d_head).swapaxes(1, 2)
        out = out.reshape(b, 1, cfg.n_heads * cfg.d_head)
    attn = linear(p["wo"], out.astype(cd), compute_dtype=cd)
    return attn, {"k": k_cache, "v": v_cache}
