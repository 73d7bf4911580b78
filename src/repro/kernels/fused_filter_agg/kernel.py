"""Pallas kernel: fused filter + grouped aggregation in one VMEM pass.

TPU adaptation of the paper's 4.4.2 fusion (filter pushdown + in-place
aggregation).  A CPU engine would stream rows through a predicate then a
hash aggregate; on TPU we instead:

* tile the row stream into ``(ROWS, 128)`` VMEM blocks (lane-aligned);
* evaluate the predicate vectorized on the VPU;
* aggregate WITHOUT scatters: each row of 128 keys is compared against
  the group ids laid along sublanes, a ``(G, 128)`` one-hot, and the
  masked values are added into ``(G, 128)`` per-lane partial sums on the
  VPU — dense vector ops instead of random HBM updates;
* exploit the TPU's *sequential* grid to accumulate the partials into a
  revisited output block, initialised at grid step 0; the wrapper folds
  the 128 lanes once at the end.

The sums stay exact: every add is an f32 VPU add, never an MXU pass
(a default-precision f32 matmul rounds its operands to bf16), so integer
values sum exactly while each partial stays below 2**24.

VMEM budget per step (defaults ROWS=8, G=1024, the router's cap):
  keys/vals/filt blocks: 3 × 2 × 8×128×4B      = 24 KB
  accumulators:          2 × 2 × 1024×128×4B   = 2 MB
  one-hot temporaries:   a few × 1024×128×4B   ≈ 2 MB   → ~4 MB < 16 MB.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime import device

#: sublane rows per grid step (block covers ROWS×128 elements)
DEFAULT_BLOCK_ROWS = 8

#: the group axis lies along sublanes, so it is padded to whole sublanes
GROUP_ALIGN = 8


def _predicate(filt: jax.Array, op: str, threshold: float) -> jax.Array:
    t = jnp.asarray(threshold, filt.dtype)
    return {
        "ge": filt >= t,
        "gt": filt > t,
        "le": filt <= t,
        "lt": filt < t,
        "eq": filt == t,
        "ne": filt != t,
    }[op]


def _kernel(
    keys_ref,      # (ROWS, 128) int32
    vals_ref,      # (ROWS, 128) f32
    filt_ref,      # (ROWS, 128) f32
    sums_ref,      # (G, 128) f32 per-lane partial sums (revisited block)
    counts_ref,    # (G, 128) f32 per-lane partial counts
    *,
    op: str,
    threshold: float,
    num_groups: int,
    block_rows: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    keys = keys_ref[...]
    mask = _predicate(filt_ref[...], op, threshold)
    vals = jnp.where(mask, vals_ref[...].astype(jnp.float32), 0.0)
    ones = mask.astype(jnp.float32)

    # group id g on sublane g; padded rows carry key == -1 and match nothing
    group_ids = jax.lax.broadcasted_iota(jnp.int32, (num_groups, 128), 0)
    sums = jnp.zeros((num_groups, 128), jnp.float32)
    counts = jnp.zeros((num_groups, 128), jnp.float32)
    for r in range(block_rows):
        hit = keys[r : r + 1, :] == group_ids
        sums += jnp.where(hit, vals[r : r + 1, :], 0.0)
        counts += jnp.where(hit, ones[r : r + 1, :], 0.0)
    sums_ref[...] += sums
    counts_ref[...] += counts


def fused_filter_agg_kernel(
    keys2d: jax.Array,   # (R, 128) int32, padded rows = -1
    vals2d: jax.Array,   # (R, 128) f32
    filt2d: jax.Array,   # (R, 128) f32
    *,
    op: str,
    threshold: float,
    num_groups: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> Tuple[jax.Array, jax.Array]:
    """Per-lane partial ``(sums, counts)``, each ``(num_groups, 128)``."""
    rows = keys2d.shape[0]
    assert rows % block_rows == 0, (rows, block_rows)
    assert num_groups % GROUP_ALIGN == 0, "group axis must fill whole sublanes"
    row_block = pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    acc_block = pl.BlockSpec((num_groups, 128), lambda i: (0, 0))
    acc_shape = jax.ShapeDtypeStruct((num_groups, 128), jnp.float32)
    sums, counts = pl.pallas_call(
        functools.partial(
            _kernel, op=op, threshold=threshold, num_groups=num_groups,
            block_rows=block_rows,
        ),
        grid=(rows // block_rows,),
        in_specs=[row_block, row_block, row_block],
        out_specs=[acc_block, acc_block],
        out_shape=[acc_shape, acc_shape],
        interpret=device.pallas_interpret(),
    )(keys2d, vals2d, filt2d)
    return sums, counts
