"""SQL v2 joined queries: parallel columnar feed + fused-kernel A/B.

Two scenarios pin the interactive multi-table path (``client.query`` with
zero registration) introduced with SQL v2:

* **joined_query** — JOIN + WHERE + GROUP BY + SUM over the trips/zones
  pair at reasonable-scale row counts, cold (first call, includes parse/
  route/compile) vs warm, then a kernel-vs-jnp A/B on the exec phase
  (isolated via the ``QueryExecuted`` telemetry breakdown).  Results are
  asserted byte-identical across engines — the kernel route is a perf
  knob, never a semantics knob.  On the CPU the kernel runs in the
  Pallas interpreter (``runtime/device.py``), so its absolute numbers
  there carry interpreter overhead.
* **pooled_scan** — the joined query's table scans with object-store GET
  latency restored (see ``bench_parallel_dag._S3LikeStore``), serial vs
  pooled with kernel-sized work items (``KERNEL_CHUNK_ROWS``).
  Acceptance: **>= 2x wall-clock for the pooled feed**, byte-identical
  concatenation.

Also runnable standalone for the CI smoke-bench job::

    python -m benchmarks.bench_sql_join --smoke --json out.json
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional

import numpy as np

from benchmarks.bench_parallel_dag import _S3LikeStore
from benchmarks.common import bench, perf_meta, row
from repro.api import Client
from repro.runtime.device import pallas_interpret
from repro.table import Predicate, TableFormat, execute_scan, plan_scan
from repro.table.scan import KERNEL_CHUNK_ROWS
from repro.table.schema import Schema

#: group-key cardinality (well under route.py's 1024-group ceiling)
N_ZONES = 256

JOIN_SQL = """
SELECT z.borough, COUNT(*) AS trips, SUM(t.fare) AS total_fare
FROM trips AS t JOIN zones AS z ON t.zone = z.zone_id
WHERE t.distance > 5
GROUP BY z.borough ORDER BY z.borough
"""


def _make_tables(n: int, rng: np.random.Generator) -> Dict[str, Dict]:
    # int32 columns with value ranges the router can prove f32-exact at
    # this row count (max * n < 2^24), so engine="auto" takes the kernel
    return {
        "trips": {
            "zone": rng.integers(0, N_ZONES, n).astype(np.int32),
            "fare": rng.integers(1, 64, n).astype(np.int32),
            "distance": rng.integers(0, 30, n).astype(np.int32),
        },
        "zones": {
            "zone_id": np.arange(N_ZONES, dtype=np.int32),
            "borough": (np.arange(N_ZONES, dtype=np.int32) % 16) + 100,
        },
    }


def _exec_s(client: Client, engine: str, iters: int = 3) -> float:
    """Min exec-phase seconds over ``iters`` warm calls, read from the
    query's own ``QueryExecuted`` telemetry breakdown."""
    best = float("inf")
    for _ in range(iters):
        client.query(JOIN_SQL, engine=engine)
        ev = [e for e in client.events() if type(e).__name__ == "QueryExecuted"][-1]
        assert ev.engine_path == ("kernel" if engine == "kernel" else "jnp")
        best = min(best, ev.exec_s)
    return best


def _joined_query(n: int, rng: np.random.Generator) -> Dict:
    data = _make_tables(n, rng)
    with Client.ephemeral() as client:
        for name, cols in data.items():
            client.write_table(name, cols)

        t0 = time.perf_counter()
        cold = client.query(JOIN_SQL)  # auto -> kernel on this data
        cold_s = time.perf_counter() - t0
        ev = [e for e in client.events() if type(e).__name__ == "QueryExecuted"][-1]
        assert ev.engine_path == "kernel", (
            f"auto should route this query to the kernel, got {ev.engine_path!r}"
        )

        warm_s = bench(lambda: client.query(JOIN_SQL), warmup=0, iters=3)
        by_engine = {
            eng: client.query(JOIN_SQL, engine=eng) for eng in ("kernel", "jnp")
        }
        for k in cold:
            np.testing.assert_array_equal(by_engine["kernel"][k], by_engine["jnp"][k])
            assert by_engine["kernel"][k].dtype == by_engine["jnp"][k].dtype
            np.testing.assert_array_equal(cold[k], by_engine["jnp"][k])

        kernel_exec_s = _exec_s(client, "kernel")
        jnp_exec_s = _exec_s(client, "jnp")
    # even with interpreter overhead the one-hot kernel pipeline beats the
    # sort-based jnp groupby at these shapes (~1.9x observed); hold the
    # conservative "no slower" floor so a routing regression (kernel path
    # silently degrading) fails the smoke bench
    assert jnp_exec_s / max(kernel_exec_s, 1e-9) >= 1.0, (
        f"kernel exec {kernel_exec_s:.4f}s slower than jnp {jnp_exec_s:.4f}s"
    )
    return {
        "rows": n,
        "groups": int(len(cold["borough"])),
        "cold_s": cold_s,
        "warm_s": warm_s,
        "kernel_exec_s": kernel_exec_s,
        "jnp_exec_s": jnp_exec_s,
        "kernel_vs_jnp": jnp_exec_s / max(kernel_exec_s, 1e-9),
        "interpret_mode": pallas_interpret(),
        "engines_byte_identical": True,
    }


def _pooled_scan(n: int, rng: np.random.Generator) -> Dict:
    """Serial vs pooled+chunked reads of the joined query's two scans."""
    n_scan = max(n * 2, 100_000)
    shard_rows = max(2048, n_scan // 48)  # ~48 shards to overlap
    fmt = TableFormat(
        _S3LikeStore(tempfile.mkdtemp(prefix="repro_sqljoin_")),
        shard_rows=shard_rows,
    )
    data = _make_tables(n_scan, rng)
    snaps = {
        name: fmt.write(
            name,
            Schema.of(**{c: str(a.dtype) for c, a in cols.items()}),
            cols,
        )
        for name, cols in data.items()
    }
    # exactly the plans Runner.query builds: pruned columns + the pushed
    # primary-table conjunct
    plans = {
        "trips": plan_scan(
            snaps["trips"],
            columns=["zone", "fare"],
            predicates=[Predicate("distance", ">", 5)],
        ),
        "zones": plan_scan(snaps["zones"], columns=["zone_id", "borough"]),
    }

    def scan_all(pool, chunk_rows):
        return {
            t: execute_scan(fmt, p, pool=pool, chunk_rows=chunk_rows)
            for t, p in plans.items()
        }

    with ThreadPoolExecutor(max_workers=8, thread_name_prefix="scan") as pool:
        serial = scan_all(None, None)
        pooled = scan_all(pool, KERNEL_CHUNK_ROWS)
        for t in serial:
            for c in serial[t]:
                np.testing.assert_array_equal(serial[t][c], pooled[t][c])
        t_serial = bench(lambda: scan_all(None, None), warmup=1, iters=3)
        t_pooled = bench(
            lambda: scan_all(pool, KERNEL_CHUNK_ROWS), warmup=1, iters=3
        )
    speedup = t_serial / max(t_pooled, 1e-9)
    assert speedup >= 2.0, (
        f"pooled joined-scan speedup {speedup:.2f}x < 2x acceptance floor"
    )
    return {
        "rows": n_scan,
        "shards": sum(len(p.shards) for p in plans.values()),
        "chunk_rows": KERNEL_CHUNK_ROWS,
        "get_latency_s": _S3LikeStore.GET_LATENCY_S,
        "serial_wall_s": t_serial,
        "pooled_wall_s": t_pooled,
        "speedup": speedup,
    }


def run(n: int = 200_000, json_path: Optional[str] = None) -> List[str]:
    rng = np.random.default_rng(0)
    out: List[str] = []

    q = _joined_query(n, rng)
    out.append(
        row(
            "sql_join_query",
            q["warm_s"] * 1e6,
            f"rows={q['rows']};groups={q['groups']};cold_s={q['cold_s']:.3f};"
            f"kernel_exec_s={q['kernel_exec_s']:.4f};"
            f"jnp_exec_s={q['jnp_exec_s']:.4f};"
            f"kernel_vs_jnp={q['kernel_vs_jnp']:.2f}x;"
            f"interpreted={q['interpret_mode']};"
            "byte_identical=yes",
        )
    )

    s = _pooled_scan(n, rng)
    out.append(
        row(
            "sql_join_pooled_scan",
            s["pooled_wall_s"] * 1e6,
            f"rows={s['rows']};shards={s['shards']};"
            f"serial_s={s['serial_wall_s']:.3f};"
            f"speedup={s['speedup']:.2f}x(>=2x asserted)",
        )
    )

    if json_path is not None:
        results = {
            "benchmark": "sql_join",
            "n": n,
            "scenarios": {
                "joined_query": {
                    **q,
                    **perf_meta(parallelism=1, wall_s=q["warm_s"]),
                },
                "pooled_scan": {
                    **s,
                    **perf_meta(
                        parallelism=8,
                        wall_s=s["pooled_wall_s"],
                        sequential_wall_s=s["serial_wall_s"],
                    ),
                },
            },
        }
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small row count for CI")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write scenario metrics as JSON")
    args = ap.parse_args()
    for line in run(n=20_000 if args.smoke else 200_000, json_path=args.json):
        print(line)
