"""Query-engine operator microbenchmarks (the duckdb-of-spare-parts) +
the fused_filter_agg Pallas kernel vs its oracle and vs the engine path.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench, row
from repro.engine import Columnar, Query, col, compile_query


def run(n: int = 1_000_000) -> List[str]:
    out = []
    rng = np.random.default_rng(0)
    rel = Columnar.from_numpy(
        {
            "k": rng.integers(0, 256, n).astype(np.int32),
            "k2": rng.integers(0, 16, n).astype(np.int32),
            "v": rng.random(n).astype(np.float32),
        }
    )
    cases = {
        "filter": Query("t").where(col("v") > 0.5).select("v"),
        "groupby_sum": Query("t").group_by("k").agg("sum", col("v"), "s"),
        "filter_groupby_sort": (
            Query("t").where(col("v") > 0.5).group_by("k")
            .agg("sum", col("v"), "s").count("n").sort("s", desc=True)
        ),
        "multikey_groupby": (
            Query("t").group_by("k", "k2").agg("mean", col("v"), "m")
        ),
    }
    for name, q in cases.items():
        fn = compile_query(q)
        fn(rel)  # compile

        def call(fn=fn):
            jax.block_until_ready(fn(rel).valid)

        t = bench(call, warmup=1, iters=5)
        out.append(row(f"engine_{name}_n{n}", t * 1e6, f"rows_per_s={n / t:.2e}"))

    # Pallas fused kernel (interpreted on the CPU — correctness/structure,
    # not TPU speed) vs the pure-jnp oracle
    from repro.kernels.fused_filter_agg import fused_filter_agg, fused_filter_agg_ref
    from repro.runtime.device import pallas_interpret

    keys = jnp.asarray(rng.integers(0, 256, 131072).astype(np.int32))
    vals = jnp.asarray(rng.random(131072).astype(np.float32))
    filt = jnp.asarray(rng.random(131072).astype(np.float32))

    def kernel_call():
        s, c = fused_filter_agg(
            keys, vals, filt, op="ge", threshold=0.5, num_groups=256,
        )
        jax.block_until_ready(s)

    def ref_call():
        s, c = fused_filter_agg_ref(
            keys, vals, filt, op="ge", threshold=0.5, num_groups=256
        )
        jax.block_until_ready(s)

    tk = bench(kernel_call, warmup=1, iters=3)
    tr = bench(ref_call, warmup=1, iters=3)
    out.append(
        row(
            "kernel_fused_filter_agg_131k",
            tk * 1e6,
            f"ref_us={tr * 1e6:.0f};interpreted={pallas_interpret()}",
        )
    )
    return out
