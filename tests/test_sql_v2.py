"""SQL v2: joins, richer grammar, kernel routing, zero registration.

Four contracts under test:

* the grammar parses JOIN/OR/IN/BETWEEN with *positioned* SqlErrors for
  everything it rejects (trailing garbage, reserved-word aliases,
  composite ON conditions, multiple statements);
* join execution matches a numpy oracle — first-match gather semantics,
  inner drop / left zero-fill for misses;
* the kernel route is byte-identical to the jnp reference wherever
  ``engine="auto"`` takes it (and the router refuses everything it
  cannot prove exact), across dtypes, group cardinalities, empty-after-
  filter, and parallelism levels — the kernel route never touches
  artifacts or fingerprints, and a node whose dense group-by sums floats
  is keyed by its group path;
* ``client.query`` resolves every table name against the catalog with
  zero registration, scans through the pooled chunked feed, and reports
  its engine path + phase breakdown on ``QueryExecuted``.
"""
import numpy as np
import pytest

from repro.api import Client
from repro.core import Pipeline
from repro.core.physical import PlannerConfig
from repro.engine import Columnar, compile_query, execute_query, parse_sql
from repro.engine.route import (
    RouteDecision,
    RouteError,
    plan_route,
)
from repro.engine.sql import SqlError
from repro.runtime import ExecutorConfig

N_TRIPS = 3_000
N_ZONES = 16


def _trips(rng, n=N_TRIPS, fare_dtype=np.int32):
    return {
        "zone": rng.integers(0, N_ZONES, n).astype(np.int32),
        "fare": rng.integers(1, 50, n).astype(fare_dtype),
        "dist": rng.integers(0, 30, n).astype(np.int32),
    }


def _zones(n=N_ZONES):
    return {
        "zone_id": np.arange(n, dtype=np.int32),
        "borough": (np.arange(n, dtype=np.int32) % 4) + 100,
    }


JOIN_SQL = """
SELECT z.borough, COUNT(*) AS count, SUM(t.fare) AS total
FROM trips AS t JOIN zones AS z ON t.zone = z.zone_id
WHERE t.dist > 5 GROUP BY z.borough ORDER BY z.borough
"""


# --------------------------------------------------------------- grammar
def test_parse_join_clause():
    q = parse_sql(JOIN_SQL)
    assert q.source == "trips" and q.source_alias == "t"
    (j,) = q.joins
    assert (j.table, j.alias, j.how) == ("zones", "z", "inner")
    assert (j.left_on, j.right_on) == ("t.zone", "z.zone_id")
    assert q.source_tables() == ["trips", "zones"]


def test_parse_join_orientation_flipped():
    q = parse_sql(
        "SELECT * FROM trips AS t JOIN zones AS z ON z.zone_id = t.zone"
    )
    (j,) = q.joins
    assert (j.left_on, j.right_on) == ("t.zone", "z.zone_id")


def test_parse_left_join():
    for kw in ("LEFT JOIN", "LEFT OUTER JOIN"):
        q = parse_sql(
            f"SELECT * FROM trips AS t {kw} zones AS z ON t.zone = z.zone_id"
        )
        assert q.joins[0].how == "left"


def test_composite_on_condition_rejected():
    with pytest.raises(SqlError, match="composite join conditions"):
        parse_sql(
            "SELECT * FROM a JOIN b ON a.x = b.x AND a.y = b.y"
        )


@pytest.mark.parametrize(
    "sql, match",
    [
        ("SELECT fare FROM trips ORDER BY fare ASC 42", "trailing"),
        ("SELECT fare FROM trips; SELECT 1", "multiple SQL statements"),
        ("SELECT fare AS select FROM trips", "reserved"),
        ("SELECT fare FROM trips AS group", "reserved"),
        ("SELECT fare FROM trips LIMIT 5x", "LIMIT"),
    ],
)
def test_positioned_syntax_errors(sql, match):
    with pytest.raises(SqlError, match=match) as exc:
        parse_sql(sql)
    e = exc.value
    assert 0 <= e.pos <= len(sql)
    assert e.fragment  # carries the offending region


def test_trailing_semicolon_ok():
    q = parse_sql("SELECT fare FROM trips;")
    assert q.source == "trips"


def test_agg_alias_count_stays_legal():
    # the paper's Appendix SQL aliases to reserved agg names
    q = parse_sql("SELECT passenger_count AS count FROM taxi_table")
    assert q.projections[0][0] == "count"


def test_or_in_between_vs_numpy(rng):
    rel = Columnar.from_numpy(_trips(rng))
    zone = np.asarray(rel.columns["zone"])
    fare = np.asarray(rel.columns["fare"])
    dist = np.asarray(rel.columns["dist"])
    cases = {
        "SELECT fare FROM t WHERE zone = 3 OR fare > 40":
            (zone == 3) | (fare > 40),
        "SELECT fare FROM t WHERE zone IN (1, 4, 9)":
            np.isin(zone, [1, 4, 9]),
        "SELECT fare FROM t WHERE zone NOT IN (1, 4, 9)":
            ~np.isin(zone, [1, 4, 9]),
        "SELECT fare FROM t WHERE dist BETWEEN 10 AND 20":
            (dist >= 10) & (dist <= 20),
        "SELECT fare FROM t WHERE dist NOT BETWEEN 10 AND 20":
            ~((dist >= 10) & (dist <= 20)),
        "SELECT fare FROM t WHERE (zone = 1 OR zone = 2) AND fare < 10":
            ((zone == 1) | (zone == 2)) & (fare < 10),
    }
    for sql, mask in cases.items():
        out = execute_query(parse_sql(sql), rel).to_numpy()
        np.testing.assert_array_equal(out["fare"], fare[mask], err_msg=sql)


# --------------------------------------------------- join exec vs oracle
def _join_oracle(trips, zones, how):
    """First-match gather oracle in plain numpy."""
    lookup = {}
    for i, k in enumerate(zones["zone_id"]):
        lookup.setdefault(int(k), i)  # first match wins
    rows = []
    for i, k in enumerate(trips["zone"]):
        j = lookup.get(int(k))
        if j is None and how == "inner":
            continue
        rows.append((i, j))
    out = {c: trips[c][[i for i, _ in rows]] for c in trips}
    for c in zones:
        vals = np.array(
            [zones[c][j] if j is not None else 0 for _, j in rows],
            dtype=zones[c].dtype,
        )
        out[c] = vals
    return out


@pytest.mark.parametrize("how", ["inner", "left"])
def test_join_matches_oracle(rng, how):
    trips = _trips(rng, n=400)
    zones = _zones()
    # duplicate right keys (first match must win) + missing left keys
    zones["zone_id"] = np.concatenate(
        [zones["zone_id"][: N_ZONES - 4], zones["zone_id"][:4]]
    )
    trips["zone"][:25] = 99  # no match in zones
    kw = "JOIN" if how == "inner" else "LEFT JOIN"
    sql = (
        "SELECT t.zone, t.fare, z.borough FROM trips AS t "
        f"{kw} zones AS z ON t.zone = z.zone_id"
    )
    out = compile_query(parse_sql(sql))(
        Columnar.from_numpy(trips), {"zones": Columnar.from_numpy(zones)}
    ).to_numpy()
    want = _join_oracle(trips, zones, how)
    np.testing.assert_array_equal(out["zone"], want["zone"])
    np.testing.assert_array_equal(out["fare"], want["fare"])
    np.testing.assert_array_equal(out["borough"], want["borough"])


def test_join_key_dtype_checked(rng):
    trips = {"zone": (rng.random(16)).astype(np.float32)}
    zones = _zones()
    sql = "SELECT * FROM trips AS t JOIN zones AS z ON t.zone = z.zone_id"
    with pytest.raises(TypeError, match="join"):
        execute_query(
            parse_sql(sql),
            Columnar.from_numpy(trips),
            joined={"zones": Columnar.from_numpy(zones)},
        )


# --------------------------------------------------------------- routing
def _stats(**kv):
    return dict(kv)


def test_route_auto_takes_kernel_when_exact():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    r = plan_route(
        q, stats=_stats(zone=(0, 15), fare=(1, 50)), total_rows=10_000
    )
    assert r.engine_path == "kernel"
    assert r.num_groups >= 16


def test_route_auto_refuses_floats():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    # fare absent from stats = not a kernel-safe dtype (float column)
    r = plan_route(q, stats=_stats(zone=(0, 15)), total_rows=10_000)
    assert r.engine_path == "jnp"


def test_route_auto_refuses_wide_key_range():
    q = parse_sql("SELECT zone, COUNT(*) AS n FROM t GROUP BY zone")
    r = plan_route(q, stats=_stats(zone=(0, 10**6)), total_rows=1_000)
    assert r.engine_path == "jnp"


def test_route_auto_refuses_overflow_risk():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    r = plan_route(
        q, stats=_stats(zone=(0, 15), fare=(0, 2**20)), total_rows=2**20
    )
    assert r.engine_path == "jnp"


def test_route_jnp_pins_reference_path():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    r = plan_route(
        q, engine="jnp", stats=_stats(zone=(0, 15), fare=(1, 50)),
        total_rows=100,
    )
    assert r.engine_path == "jnp"


def test_route_forced_kernel_raises_on_structural_miss():
    q = parse_sql("SELECT zone, dist, COUNT(*) AS n FROM t GROUP BY zone, dist")
    with pytest.raises(RouteError):
        plan_route(q, engine="kernel", stats=_stats(zone=(0, 3), dist=(0, 3)))


# ------------------------------------------- kernel/jnp parity (matrix)
def _parity_case(rng, *, n, groups, key_dtype, sql):
    rel = Columnar.from_numpy({
        "zone": rng.integers(0, groups, n).astype(key_dtype),
        "fare": rng.integers(1, 50, n).astype(np.int32),
        "dist": rng.integers(0, 30, n).astype(np.int32),
    })
    q = parse_sql(sql)
    kmax = groups - 1
    route = plan_route(
        q, engine="kernel",
        stats=_stats(zone=(0, kmax), fare=(1, 50), dist=(0, 30)),
        total_rows=n,
    )
    got = execute_query(q, rel, route=route).to_numpy()
    want = execute_query(q, rel).to_numpy()
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)
        assert got[k].dtype == want[k].dtype, k


PARITY_SQL = (
    "SELECT zone, COUNT(*) AS n, SUM(fare) AS s, AVG(fare) AS m "
    "FROM t WHERE dist > 5 GROUP BY zone"
)


@pytest.mark.parametrize("key_dtype", [np.int32, np.int8, np.bool_])
def test_kernel_parity_key_dtypes(rng, key_dtype):
    groups = 2 if key_dtype is np.bool_ else 13
    _parity_case(
        rng, n=700, groups=groups, key_dtype=key_dtype, sql=PARITY_SQL
    )


@pytest.mark.parametrize("groups", [1, 7, 128, 1000])
def test_kernel_parity_group_cardinalities(rng, groups):
    _parity_case(
        rng, n=2_000, groups=groups, key_dtype=np.int32, sql=PARITY_SQL
    )


def test_kernel_parity_empty_after_filter(rng):
    _parity_case(
        rng, n=300, groups=8, key_dtype=np.int32,
        sql="SELECT zone, COUNT(*) AS n, SUM(fare) AS s FROM t "
            "WHERE dist > 1000 GROUP BY zone",
    )


def test_kernel_parity_unfiltered_and_count_only(rng):
    for sql in (
        "SELECT zone, SUM(fare) AS s FROM t GROUP BY zone",
        "SELECT zone, COUNT(*) AS n FROM t GROUP BY zone",
    ):
        _parity_case(rng, n=900, groups=11, key_dtype=np.int32, sql=sql)


def test_auto_falls_back_at_exactness_boundary(rng):
    """Right at the f32-exactness boundary auto must choose jnp; the
    forced kernel on safe data stays byte-identical (fallback boundary)."""
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    n = 4_096
    safe = plan_route(
        q, stats=_stats(zone=(0, 3), fare=(0, (2**24 // n) - 1)), total_rows=n
    )
    unsafe = plan_route(
        q, stats=_stats(zone=(0, 3), fare=(0, 2**24 // n + 1)), total_rows=n
    )
    assert safe.engine_path == "kernel"
    assert unsafe.engine_path == "jnp"


# --------------------------------------------- zero-registration client
@pytest.fixture
def lake(tmp_path, rng):
    with Client(tmp_path / "lake") as client:
        client.write_table("trips", _trips(rng))
        client.write_table("zones", _zones())
        yield client


def test_client_join_query_zero_registration(lake):
    out = lake.query(JOIN_SQL)
    # regenerate the fixture's data with the same seed (the lake fixture
    # consumed the shared rng's first draws)
    trips, zones = _trips(np.random.default_rng(0)), _zones()
    borough = zones["borough"][trips["zone"]]
    mask = trips["dist"] > 5
    for i, b in enumerate(out["borough"]):
        sel = mask & (borough == b)
        assert out["count"][i] == sel.sum()
        assert out["total"][i] == trips["fare"][sel].sum()


def test_client_engine_parity_and_telemetry(lake):
    results = {e: lake.query(JOIN_SQL, engine=e) for e in ("auto", "kernel", "jnp")}
    for k in results["jnp"]:
        for e in ("auto", "kernel"):
            np.testing.assert_array_equal(results[e][k], results["jnp"][k])
            assert results[e][k].dtype == results["jnp"][k].dtype
    evs = [e for e in lake.events() if type(e).__name__ == "QueryExecuted"]
    assert [e.engine_path for e in evs[-3:]] == ["kernel", "kernel", "jnp"]
    last = evs[-1]
    assert last.parse_s > 0 and last.plan_s > 0
    assert last.scan_s > 0 and last.exec_s > 0
    assert last.parse_s + last.plan_s + last.scan_s + last.exec_s <= last.wall_s


def test_client_unknown_names_are_sql_errors(lake):
    with pytest.raises(SqlError, match="unknown table 'nope'"):
        lake.query("SELECT x FROM nope")
    with pytest.raises(SqlError, match="unknown column 'missing'"):
        lake.query("SELECT missing FROM trips")
    with pytest.raises(SqlError, match="no column 'missing'"):
        lake.query(
            "SELECT z.missing FROM trips AS t JOIN zones AS z "
            "ON t.zone = z.zone_id"
        )
    with pytest.raises(SqlError, match="unknown table qualifier"):
        lake.query("SELECT q.fare FROM trips AS t")


def test_client_select_star_over_join(lake):
    out = lake.query(
        "SELECT * FROM trips AS t JOIN zones AS z ON t.zone = z.zone_id "
        "LIMIT 5"
    )
    # plain names where unique; both tables' columns present
    assert set(out) == {"zone", "fare", "dist", "zone_id", "borough"}
    assert all(len(v) == 5 for v in out.values())


# ---------------------------- pipeline parity: parallelism x engine
def _float_trips(rng, n=N_TRIPS):
    """Trips whose fares have fractions, so their f32 sums depend on the
    order of addition."""
    trips = _trips(rng, n)
    trips["fare"] = (rng.random(n) * 50 + 1).astype(np.float32)
    return trips


def _run_join_pipeline(parallelism, sql_engine, rng, trips=_trips):
    p = Pipeline("sql_v2_parity")
    p.sql("by_borough", JOIN_SQL, materialize=True)
    with Client.ephemeral(
        shard_rows=512,
        executor_config=ExecutorConfig(
            max_workers=8, max_concurrent_stages=parallelism
        ),
    ) as client:
        client.write_table("trips", trips(rng))
        client.write_table("zones", _zones())
        handle = client.run(
            p,
            parallelism=parallelism,
            planner_config=PlannerConfig(sql_engine=sql_engine),
            cache=False,
        ).raise_for_state()
        out = client.query("SELECT * FROM by_borough", engine="jnp")
        return dict(handle.artifacts), out


def test_pipeline_parity_parallelism_x_engine(rng):
    base_art, base_out = _run_join_pipeline(1, "jnp", np.random.default_rng(5))
    for parallelism in (1, 2, 8):
        for engine in ("auto", "kernel", "jnp"):
            art, out = _run_join_pipeline(
                parallelism, engine, np.random.default_rng(5)
            )
            assert art == base_art, (parallelism, engine)
            for k in base_out:
                np.testing.assert_array_equal(
                    out[k], base_out[k], err_msg=f"{parallelism}/{engine}/{k}"
                )


def test_pipeline_parity_float_sums_by_engine():
    """Float SUMs: the kernel (forced), the dense group-by (auto) and the
    sort reference (jnp) agree to f32 rounding; each engine writes the
    same artifacts at every parallelism; keys and counts are exact."""
    runs = {
        (parallelism, engine): _run_join_pipeline(
            parallelism, engine, np.random.default_rng(5), _float_trips
        )
        for parallelism in (1, 8)
        for engine in ("auto", "kernel", "jnp")
    }
    base_out = runs[(1, "jnp")][1]
    for (parallelism, engine), (art, out) in runs.items():
        assert art == runs[(1, engine)][0], (parallelism, engine)
        for k in ("borough", "count"):
            np.testing.assert_array_equal(out[k], base_out[k])
        assert out["total"].dtype == np.float32
        np.testing.assert_allclose(out["total"], base_out["total"], rtol=1e-5)


def test_engine_switch_keeps_cache_warm(rng):
    """Routing is not fingerprinted: a warm cache built under one engine
    must fully satisfy a re-run under the other."""
    p = Pipeline("sql_v2_cache")
    p.sql("by_borough", JOIN_SQL, materialize=True)
    with Client.ephemeral(shard_rows=512) as client:
        client.write_table("trips", _trips(rng))
        client.write_table("zones", _zones())
        cold = client.run(
            p, planner_config=PlannerConfig(sql_engine="kernel")
        ).raise_for_state()
        assert cold.stats["cache"]["nodes_executed"] >= 1
        warm = client.run(
            p, planner_config=PlannerConfig(sql_engine="jnp")
        ).raise_for_state()
        assert warm.stats["cache"]["nodes_executed"] == 0
        assert warm.stats["cache"]["hits"] >= 1


def test_engine_switch_float_sums_follow_group_path(rng):
    """A node whose dense group-by sums floats (auto) is keyed by its
    group path: the sort reference's entry (jnp) is never served for it,
    nor the other way round, and each stays warm for its own path.  The
    kernel and the reference path still share one entry."""
    p = Pipeline("sql_v2_cache_float")
    p.sql("by_borough", JOIN_SQL, materialize=True)
    with Client.ephemeral(shard_rows=512) as client:
        client.write_table("trips", _float_trips(rng))
        client.write_table("zones", _zones())

        def executed(engine):
            handle = client.run(
                p, planner_config=PlannerConfig(sql_engine=engine)
            ).raise_for_state()
            return handle.stats["cache"]["nodes_executed"]

        assert executed("auto") >= 1
        assert executed("jnp") >= 1
        assert executed("auto") == 0
        assert executed("jnp") == 0
        assert executed("kernel") == 0


def test_single_table_fingerprints_unchanged():
    """v2 must not perturb the single-table query population's JSON form
    (node fingerprints hash it — the differential cache stays warm)."""
    q = parse_sql("SELECT fare FROM trips WHERE dist > 5")
    d = q.to_json_dict()
    assert "joins" not in d and "source_alias" not in d
    d2 = parse_sql(JOIN_SQL).to_json_dict()
    assert "joins" in d2 and d2["source_alias"] == "t"


# ------------------------------------------------------ lineage goldens
def test_lineage_join_golden_report():
    from repro.analysis.lint import lint_pipeline
    from repro.table.schema import Schema

    ext = {
        "trips": Schema.of(zone="int32", fare="int32", dist="int32"),
        "zones": Schema.of(zone_id="int32", borough="int32"),
    }
    p = Pipeline("lineage_joins")
    p.sql("ok", JOIN_SQL)
    p.sql(
        "bad_col",
        "SELECT z.missing FROM trips AS t JOIN zones AS z "
        "ON t.zone = z.zone_id",
    )
    p.sql(
        "bad_order",
        "SELECT t.fare FROM trips AS t JOIN zones AS z "
        "ON t.zone = z.zone_id ORDER BY z.borough",
    )
    rep = lint_pipeline(p, external_schemas=ext)
    got = sorted((f.rule, f.node) for f in rep.findings)
    assert got == [("L001", "bad_col"), ("L003", "bad_order")]
    (l001,) = [f for f in rep.findings if f.rule == "L001"]
    assert "'zones'" in l001.message  # attributed to the owning table


def test_lineage_propagates_join_schemas():
    from repro.analysis.lineage import propagate_schema
    from repro.table.schema import Schema

    ext = {
        "trips": Schema.of(zone="int32", fare="int32", dist="int32"),
        "zones": Schema.of(zone_id="int32", borough="int32"),
    }
    p = Pipeline("lineage_schemas")
    agg = p.sql("agg", JOIN_SQL)
    star = p.sql(
        "star",
        "SELECT * FROM trips AS t JOIN zones AS z ON t.zone = z.zone_id",
    )
    out = propagate_schema(agg, ext)
    assert [(c.name, c.dtype) for c in out.columns] == [
        ("borough", "int32"), ("count", "int32"), ("total", "int32")
    ]
    out_star = propagate_schema(star, ext)
    assert out_star.names == ["zone", "fare", "dist", "zone_id", "borough"]


def test_lineage_l004_covers_join_tables():
    from repro.analysis.lint import lint_pipeline

    p = Pipeline("lineage_l004")
    p.sql(
        "j",
        "SELECT * FROM trips AS t JOIN nowhere AS n ON t.zone = n.zone_id",
    )
    rep = lint_pipeline(p, external_schemas={})
    assert {f.rule for f in rep.findings} >= {"L004"}
    assert any("nowhere" in f.message for f in rep.findings)


# --------------------------------------------------- telemetry/back-compat
def test_query_executed_event_roundtrip_and_backcompat():
    from repro.telemetry.events import QueryExecuted, event_from_json_dict

    ev = QueryExecuted(
        table="trips", rows_out=4, shards_read=2, wall_s=0.5,
        engine_path="kernel", parse_s=0.01, plan_s=0.02, scan_s=0.3,
        exec_s=0.1,
    )
    back = event_from_json_dict(ev.to_json_dict())
    assert back == ev
    # a pre-v2 run log (no engine_path/phase fields) still loads
    old = {"kind": "QueryExecuted", "table": "t", "rows_out": 1,
           "shards_read": 1, "wall_s": 0.1}
    legacy = event_from_json_dict(old)
    assert legacy.engine_path == "jnp" and legacy.exec_s == 0.0


# --------------------------------------------------------- chunked scans
def test_execute_scan_chunk_rows_preserves_bytes(fmt, rng):
    from concurrent.futures import ThreadPoolExecutor

    from repro.table import execute_scan, plan_scan
    from repro.table.schema import Schema

    data = _trips(rng, n=5_000)
    snap = fmt.write(
        "trips",
        Schema.of(**{c: str(a.dtype) for c, a in data.items()}),
        data,
    )
    plan = plan_scan(snap)
    serial = execute_scan(fmt, plan)
    with ThreadPoolExecutor(max_workers=4) as pool:
        for chunk_rows in (1, 128, 8192, 10**9):
            chunked = execute_scan(fmt, plan, pool=pool, chunk_rows=chunk_rows)
            for c in serial:
                np.testing.assert_array_equal(serial[c], chunked[c])
