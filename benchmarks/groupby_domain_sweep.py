"""Dense against sort-based group-by, by the size of the group domain.

The evidence for ``DENSE_MAX_GROUPS`` in ``engine/route.py``.  Times two
aggregate sets over 5,916,591 rows (what TPC-H Q1 at SF1 hands the
device) grouped by two integer keys whose domain has G slots, once
through the dense path (``RouteDecision.group_domain``) and once through
the sort path (no route): TPC-H Q1's eight aggregates at every G of
``DOMAINS``, and MIN/MAX of four columns, whose masked reductions fill
with ``_extreme`` instead of 0, at the smallest and the largest G.  The
sort path hardly depends on G, so it is timed at a few sizes only.
Prints one JSON object a line: aggregates, G, path, first-call and
median call seconds (a call waits for its outputs).

Run on a TPU host: ``PYTHONPATH=src python3 benchmarks/groupby_domain_sweep.py``.
"""
from __future__ import annotations

import json
import statistics
import sys
import time

ROWS = 5_916_591
CALLS = 5

#: (G, sizes of the two keys' domains); G=1 is a global aggregation
DOMAINS = ((1, ()), (6, (3, 2)), (64, (32, 2)), (265, (53, 5)),
           (1024, (512, 2)), (4096, (2048, 2)), (16384, (8192, 2)))

Q1_AGGS = (
    "SUM(l_quantity) AS sum_qty, SUM(l_extendedprice) AS sum_base_price, "
    "SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
    "SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge, "
    "AVG(l_quantity) AS avg_qty, AVG(l_extendedprice) AS avg_price, "
    "AVG(l_discount) AS avg_disc, COUNT(*) AS count_order"
)
MIN_MAX_AGGS = (
    "MIN(l_quantity) AS min_qty, MAX(l_quantity) AS max_qty, "
    "MIN(l_extendedprice) AS min_price, MAX(l_extendedprice) AS max_price, "
    "MIN(l_discount) AS min_disc, MAX(l_tax) AS max_tax"
)

#: (name, aggregates, the Gs timed on the dense path, those on the sort path)
CASES = (
    ("q1", Q1_AGGS, tuple(g for g, _ in DOMAINS), (6, 265, 4096)),
    ("min_max", MIN_MAX_AGGS, (6, 16384), (16384,)),
)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from repro.engine.columnar import Columnar
    from repro.engine.exec import execute_query
    from repro.engine.route import RouteDecision
    from repro.engine.sql import parse_sql

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here ({device.platform})", file=sys.stderr)
        return 2
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    base = {
        "l_quantity": jax.random.randint(ks[0], (ROWS,), 1, 51, jnp.int32),
        "l_extendedprice": jax.random.uniform(ks[1], (ROWS,), jnp.float32, 900, 1e5),
        "l_discount": jax.random.randint(ks[2], (ROWS,), 0, 11).astype(jnp.float32) / 100,
        "l_tax": jax.random.randint(ks[3], (ROWS,), 0, 9).astype(jnp.float32) / 100,
    }
    valid = jax.random.uniform(ks[5], (ROWS,)) < 0.986
    for case, aggs, dense_at, sort_at in CASES:
        for g, sizes in DOMAINS:
            keys = [f"k{i}" for i in range(len(sizes))]
            cols = dict(base)
            for i, (k, size) in enumerate(zip(keys, sizes)):
                cols[k] = jax.random.randint(
                    jax.random.fold_in(ks[4], i), (ROWS,), 0, size, jnp.int32)
            rel = Columnar(cols, valid)
            by = f" GROUP BY {', '.join(keys)}" if keys else ""
            query = parse_sql(f"SELECT {', '.join(keys + [aggs])} FROM lineitem{by}")
            dense = RouteDecision("jnp", "sweep",
                                  group_domain=tuple((0, s) for s in sizes))
            for path, route, timed_at in (("dense", dense, dense_at),
                                          ("sort", None, sort_at)):
                if g not in timed_at:
                    continue
                fn = jax.jit(lambda r, route=route: execute_query(query, r, route=route))
                t0 = time.perf_counter()
                jax.block_until_ready(fn(rel))
                first_s = time.perf_counter() - t0
                times = []
                for _ in range(CALLS):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(rel))
                    times.append(time.perf_counter() - t0)
                print(json.dumps({
                    "aggs": case, "G": g, "path": path, "rows": ROWS,
                    "device": device.device_kind, "first_call_s": first_s,
                    "median_s": statistics.median(times), "min_s": min(times),
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
