"""Bring-up check: the lakehouse's write -> pipeline -> query path on one TPU.

Writes the paper's taxi table (16M rows of 2019 yellow-cab trips, 245
shards) into a fresh lake, runs ``examples/taxi_pipeline.py`` through
``Client.run`` (cold, then warm), and runs three ``Client.query``
statements: a COUNT the router sends to the fused Pallas kernel, a
SUM/AVG forced onto the kernel and compared byte for byte with the jnp
path, and a join against a small zones table.  Every result is checked
against numpy.

    python3 chip_smoke.py

Progress lines name the device, each phase's wall time, the per-query
parse/plan/scan/exec split, the compile cache and the device's peak
memory.  The last line of standard output is one JSON object naming the
device.  The script exits nonzero, and prints no such line, when JAX
finds no TPU or any check fails.  It is a bring-up check, not a
benchmark: its times include compiling.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: about two months of 2019 NYC yellow-cab volume; below 2**24 so the
#: router can prove f32 counts exact and admit the kernel under "auto"
ROWS = 16_000_000

COUNT_SQL = (
    "SELECT pickup_location_id, COUNT(*) AS n FROM taxi_table "
    "GROUP BY pickup_location_id"
)
SUM_AVG_SQL = (
    "SELECT pickup_location_id, SUM(passenger_count) AS s, "
    "AVG(passenger_count) AS a FROM taxi_table GROUP BY pickup_location_id"
)
JOIN_SQL = (
    "SELECT z.borough, COUNT(*) AS n, SUM(t.passenger_count) AS s "
    "FROM taxi_table AS t JOIN zones AS z ON t.pickup_location_id = z.zone_id "
    "GROUP BY z.borough ORDER BY z.borough"
)


#: how far (in f32 units in the last place) AVG may sit from numpy's
#: correctly rounded quotient of the same exact SUM and COUNT
AVG_ULPS = 4


class SmokeFailure(AssertionError):
    """A result disagreed with its reference, or a run did not succeed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same_bytes(a, b) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


def _ref_pickups(data):
    """COUNT(*) per (pickup, dropoff) over trips since April 1, ordered as
    the engine orders it: groups ascending, then stable by counts DESC."""
    import numpy as np

    from repro.examples_data import APRIL_1

    sel = data["pickup_at"] >= APRIL_1
    pu = data["pickup_location_id"][sel].astype(np.int64)
    do = data["dropoff_location_id"][sel].astype(np.int64)
    keys, counts = np.unique(pu * (int(do.max()) + 1) + do, return_counts=True)
    order = np.argsort(-counts, kind="stable")
    width = int(do.max()) + 1
    return {
        "pickup_location_id": (keys // width)[order],
        "dropoff_location_id": (keys % width)[order],
        "counts": counts[order],
    }


def _kernel_program(client, sql: str, engine: str) -> str:
    """Compiled text of the program ``client.query(sql, engine=engine)``
    ran, for a single-table query without WHERE (the relation is then
    the whole table)."""
    import jax
    import numpy as np

    from repro.core.physical import plan_interactive_query, resolve_query_snapshots
    from repro.engine import Columnar, compile_query, parse_sql

    query = parse_sql(sql)
    snaps = resolve_query_snapshots(client.catalog, client.fmt, query)
    plan = plan_interactive_query(query, snaps, engine=engine)
    snap = snaps[query.source]
    n = snap.num_rows
    rel = Columnar(
        {
            c: jax.ShapeDtypeStruct((n,), np.dtype(snap.schema.dtype_of(c)))
            for c in plan.scans[query.source].columns
        },
        jax.ShapeDtypeStruct((n,), np.bool_),
    )
    return compile_query(query, route=plan.route).lower(rel).compile().as_text()


def run_smoke(rows: int) -> None:
    """Every phase and check, at ``rows`` taxi trips; raises on a mismatch."""
    import jax
    import numpy as np

    import repro
    from examples.taxi_pipeline import taxi
    from repro.examples_data import TAXI_SCHEMA, make_taxi_data
    from repro.runtime import device
    from repro.telemetry import QueryExecuted

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"pallas interpreted: {device.pallas_interpret()}")

    def timed(phase, fn):
        t0 = time.perf_counter()
        out = fn()
        print(f"phase {phase}: {time.perf_counter() - t0:.3f} s", flush=True)
        return out

    rng = np.random.default_rng(0)
    data = timed("make_data", lambda: make_taxi_data(rows, rng))
    zones = {
        "zone_id": np.arange(64, dtype=np.int32),
        "borough": rng.integers(0, 6, 64).astype(np.int32),
    }

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp, \
            repro.Client(Path(tmp) / "lake") as client:
        snap = timed("write_table", lambda: client.write_table(
            "taxi_table", data, schema=TAXI_SCHEMA
        ))
        client.write_table("zones", zones)
        print(f"taxi_table: {snap.num_rows} rows in {len(snap.shards)} shards")

        # ------------------------------------------------ pipeline runs
        cold = timed("run_cold", lambda: client.run(taxi))
        check(cold.state is repro.RunState.SUCCESS, f"cold run: {cold.state}")
        got = cold.artifact("pickups")
        ref = _ref_pickups(data)
        for name, want in ref.items():
            check(np.array_equal(got[name], want), f"pickups.{name} != numpy")
        check(bool(np.all(np.diff(got["counts"]) <= 0)),
              "pickups.counts not non-increasing")
        print(f"pickups: {len(got['counts'])} groups match numpy")
        warm = timed("run_warm", lambda: client.run(taxi))
        check(warm.state is repro.RunState.SUCCESS, f"warm run: {warm.state}")
        executed = warm.cache.get("nodes_executed")
        check(executed == 0, f"warm run executed {executed} nodes")

        # ------------------------------------------------------ queries
        def query(name, sql, engine="auto"):
            with client.events(follow=True) as sub:
                out = timed(f"query_{name}", lambda: client.query(sql, engine=engine))
                (ev,) = [e for e in sub.poll() if isinstance(e, QueryExecuted)]
            print(
                f"query {name}: engine_path={ev.engine_path} "
                f"parse_s={ev.parse_s:.6f} plan_s={ev.plan_s:.6f} "
                f"scan_s={ev.scan_s:.6f} exec_s={ev.exec_s:.6f} "
                f"wall_s={ev.wall_s:.6f} shards_read={ev.shards_read}"
            )
            return out, ev.engine_path

        pu = data["pickup_location_id"]
        pc = data["passenger_count"].astype(np.int64)
        keys = np.unique(pu)
        n_ref = np.bincount(pu)[keys]
        s_ref = np.bincount(pu, weights=pc).astype(np.int64)[keys]

        out, path = query("count_auto", COUNT_SQL)
        check(path == "kernel", f"COUNT routed to {path}, not the kernel")
        check(np.array_equal(out["pickup_location_id"], keys), "COUNT keys")
        check(np.array_equal(out["n"], n_ref), "COUNT != numpy")

        kern, path = query("sum_avg_kernel", SUM_AVG_SQL, engine="kernel")
        check(path == "kernel", f"forced kernel ran {path}")
        ref_jnp, path = query("sum_avg_jnp", SUM_AVG_SQL, engine="jnp")
        check(path == "jnp", f"pinned jnp ran {path}")
        for name in ref_jnp:
            check(same_bytes(kern[name], ref_jnp[name]),
                  f"{name}: kernel and jnp results differ in bytes")
        check(np.array_equal(kern["s"], s_ref), "SUM != numpy")
        # AVG is the f32 quotient of the exact SUM and COUNT.  numpy rounds
        # that division as IEEE says; the TPU's f32 division does not
        # (up to 2 ulp away on a v5e), so AVG is held to AVG_ULPS of it
        a_ref = s_ref.astype(np.float32) / n_ref.astype(np.float32)
        ulps = np.abs(kern["a"].view(np.int32) - a_ref.view(np.int32))
        print(f"AVG vs numpy: {int(np.count_nonzero(ulps))} of {len(ulps)} "
              f"groups differ, max {int(ulps.max())} ulp")
        check(kern["a"].dtype == np.float32 and int(ulps.max()) <= AVG_ULPS,
              "AVG != numpy")

        for name, sql, engine in (("count_auto", COUNT_SQL, "auto"),
                                  ("sum_avg_kernel", SUM_AVG_SQL, "kernel")):
            text = _kernel_program(client, sql, engine)
            custom = "tpu_custom_call" in text
            print(f"program {name}: tpu_custom_call={custom}")
            check(custom or device.pallas_interpret(),
                  f"{name}: no Mosaic kernel in the compiled program")

        predicted = client.explain(JOIN_SQL).engine_path
        out, path = query("join", JOIN_SQL)
        check(path == predicted, f"join ran {path}, explain said {predicted}")
        borough = zones["borough"][pu]
        b_keys = np.unique(borough)
        check(np.array_equal(out["borough"], b_keys), "join keys")
        check(np.array_equal(out["n"], np.bincount(borough)[b_keys]), "join COUNT")
        check(np.array_equal(
            out["s"], np.bincount(borough, weights=pc).astype(np.int64)[b_keys]
        ), "join SUM")

        # ------------------------------------------------- the executor
        stats = client.executor.stats()
        print(f"executor: tasks={stats['tasks']} retries={stats['retries']} "
              f"speculated={stats['speculated']}")
        check(stats["retries"] == 0, f"{stats['retries']} hidden retries")
        cache_dir = client.compile_cache_dir
        files = sum(1 for p in cache_dir.rglob("*") if p.is_file()) \
            if cache_dir.is_dir() else 0
        print(f"compile cache: {cache_dir} ({files} files)")
    mem = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'not reported')}")


def main() -> int:
    try:
        import jax

        import repro  # noqa: F401  (the program must be beside this script)
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no device: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX runs on {devices[0].platform})",
              file=sys.stderr)
        return 1
    run_smoke(ROWS)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
