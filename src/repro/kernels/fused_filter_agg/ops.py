"""Public jit'd wrapper: padding/reshaping around the Pallas kernel."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_filter_agg.kernel import (
    DEFAULT_BLOCK_ROWS,
    GROUP_ALIGN,
    fused_filter_agg_kernel,
)

_LANES = 128


@functools.partial(
    jax.jit,
    static_argnames=("op", "threshold", "num_groups", "block_rows"),
)
def fused_filter_agg(
    keys: jax.Array,        # int32[n]
    values: jax.Array,      # float[n]
    filter_vals: jax.Array,  # float[n]
    *,
    op: str = "ge",
    threshold: float = 0.0,
    num_groups: int = 256,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> Tuple[jax.Array, jax.Array]:
    """Grouped (sum, count) over rows passing the predicate — one fused pass.

    Pads the row stream to a whole number of (block_rows × 128) tiles and
    the group axis to whole sublanes; padded rows carry key ``-1`` (matches
    no group) so they contribute nothing.  The kernel's per-lane partials
    are folded here, in f32: exact for integer values whose sums stay
    below 2**24.
    """
    n = keys.shape[0]
    num_groups_padded = num_groups + (-num_groups % GROUP_ALIGN)
    tile = block_rows * _LANES
    n_pad = -n % tile
    keys_p = jnp.pad(keys.astype(jnp.int32), (0, n_pad), constant_values=-1)
    vals_p = jnp.pad(values.astype(jnp.float32), (0, n_pad))
    filt_p = jnp.pad(filter_vals.astype(jnp.float32), (0, n_pad))
    rows = (n + n_pad) // _LANES
    sums, counts = fused_filter_agg_kernel(
        keys_p.reshape(rows, _LANES),
        vals_p.reshape(rows, _LANES),
        filt_p.reshape(rows, _LANES),
        op=op,
        threshold=threshold,
        num_groups=num_groups_padded,
        block_rows=block_rows,
    )
    return (
        sums.sum(axis=1)[:num_groups],
        counts.sum(axis=1)[:num_groups],
    )
