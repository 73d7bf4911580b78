"""The explain plane — route traces, typed checks, concurrency rules.

The contract under test is *agreement*: the static verdict
(``client.explain`` / ``repro explain``) must equal what the runtime
does — same engine_path, same RouteError byte-for-byte, same routes the
physical planner stamps onto its stages — while executing nothing and
writing nothing.  Plus golden reports for every new rule family
(T401-T404, C501-C503), noqa suppression, and the generated README
catalog.
"""
import json
from pathlib import Path

import numpy as np
import pytest

import repro
from repro.analysis import (
    CONCURRENCY_RULES,
    FUNCTION_RULES,
    LintReport,
    Severity,
    TYPE_RULES,
    lint_pipeline,
    query_type_findings,
    rule_catalog_markdown,
    run_concurrency_rules,
)
from repro.analysis.catalog import CATALOG_BEGIN, CATALOG_END
from repro.api.project import Project
from repro.cli import main
from repro.core import Pipeline
from repro.core.logical import build_logical_plan
from repro.core.physical import build_physical_plan
from repro.core.runner import RunContext
from repro.engine.route import (
    EXACT_BOUND,
    ROUTE_CHECKS,
    RouteDecision,
    RouteError,
    plan_route,
)
from repro.engine.sql import SqlError, parse_sql
from repro.table.schema import Schema
from tests.helpers_taxi import TAXI_SCHEMA, make_taxi_data

TAXI = {
    "taxi_table": Schema.of(
        pickup_at="int32",
        pickup_location_id="int32",
        passenger_count="int32",
        dropoff_location_id="int32",
    )
}

JOINED = {
    "trips": Schema.of(
        zone="int32", zone_i8="int8", score="float32", fare="int32"
    ),
    "zones": Schema.of(zone_id="int32", borough="int32", weight="int32"),
}

#: module-level shared state the C-rule tests deliberately traffic in
SHARED_LOG: list = []
TOTALS: dict = {}


def lint(pipeline, schemas=TAXI) -> LintReport:
    return lint_pipeline(pipeline, external_schemas=schemas)


def rules(report: LintReport):
    return {f.rule for f in report.findings}


# =========================================================== route traces
def test_route_trace_kernel_records_every_check():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    r = plan_route(q, stats={"zone": (0, 15), "fare": (1, 50)}, total_rows=10_000)
    assert r.engine_path == "kernel"
    assert r.trace is not None and r.trace.failed is None
    ids = [c.check for c in r.trace.checks]
    assert {"R201", "R202", "R203", "R204", "R205", "R206", "R207", "R208",
            "R209"} <= set(ids)
    assert all(c.passed for c in r.trace.checks)
    # the ids always resolve in the registry repro explain documents
    assert all(c.check in ROUTE_CHECKS for c in r.trace.checks)


def test_route_trace_bails_at_first_failed_check():
    q = parse_sql("SELECT fare FROM t WHERE zone > 3")
    r = plan_route(q)
    assert r.engine_path == "jnp"
    assert r.reason == "not an aggregation"
    last = r.trace.checks[-1]
    assert last.check == "R201" and not last.passed
    assert r.trace.failed is last
    assert last.hint  # a failed check always carries a fix


def test_route_trace_engine_jnp_is_pinned():
    q = parse_sql("SELECT zone, COUNT(*) AS n FROM t GROUP BY zone")
    r = plan_route(q, engine="jnp")
    assert r.engine_path == "jnp"
    assert [c.check for c in r.trace.checks] == ["R200"]
    assert r.trace.checks[0].passed


def test_route_forced_kernel_skips_exactness_checks():
    # float aggregate column (no stats), unknown row count: auto would
    # bail at R207/R208, a forced kernel legitimately runs anyway
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    r = plan_route(q, engine="kernel", stats={"zone": (0, 15)}, total_rows=None)
    assert r.engine_path == "kernel"
    ids = {c.check for c in r.trace.checks}
    assert "R207" not in ids and "R208" not in ids


def test_route_decision_equality_and_hash_ignore_trace():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    r = plan_route(q, stats={"zone": (0, 15), "fare": (1, 50)}, total_rows=10_000)
    bare = RouteDecision(
        engine_path=r.engine_path,
        reason=r.reason,
        num_groups=r.num_groups,
        key_offset=r.key_offset,
        native_filter=r.native_filter,
    )
    assert r.trace is not None and bare.trace is None
    assert r == bare
    assert hash(r) == hash(bare)


def test_route_error_positioned_like_sql_error():
    sql = "SELECT zone, fare, COUNT(*) AS n FROM t GROUP BY zone, fare"
    with pytest.raises(RouteError) as ei:
        plan_route(parse_sql(sql), engine="kernel", stats={"zone": (0, 9)})
    e = ei.value
    assert isinstance(e.pos, int) and e.pos > 0
    assert e.fragment and "fare" in e.fragment
    assert "position" in str(e)
    assert e.hint and "fix:" in str(e)
    assert e.trace is not None and e.trace.failed.check == "R202"


def test_route_error_min_aggregate_names_the_fix():
    sql = "SELECT zone, MIN(fare) AS m FROM t GROUP BY zone"
    with pytest.raises(RouteError) as ei:
        plan_route(parse_sql(sql), engine="kernel", stats={"zone": (0, 9)})
    e = ei.value
    assert e.trace.failed.check == "R203"
    assert "jnp" in e.hint


# ============================================================== T-rules
def test_t401_float_join_key_is_an_error():
    p = Pipeline("t401")
    p.sql(
        "bad",
        "SELECT t.fare FROM trips AS t JOIN zones AS z "
        "ON t.score = z.zone_id",
    )
    report = lint(p, JOINED)
    (f,) = report.by_rule("T401")
    assert f.severity is Severity.ERROR
    assert "t.score" in f.message and "float32" in f.message
    assert f.hint and "int32" in f.hint
    assert "t.score" in (f.snippet or "")
    assert f.file and f.file.endswith("test_explain.py") and f.line


def test_t402_join_key_widening_is_info():
    p = Pipeline("t402")
    p.sql(
        "j",
        "SELECT t.fare FROM trips AS t JOIN zones AS z "
        "ON t.zone_i8 = z.zone_id",
    )
    report = lint(p, JOINED)
    (f,) = report.by_rule("T402")
    assert f.severity is Severity.INFO
    assert "int8" in f.message and "int32" in f.message
    assert report.by_rule("T401") == []


def test_t403_row_count_crosses_exactness_boundary():
    q = parse_sql("SELECT zone, COUNT(*) AS n FROM t GROUP BY zone")
    schemas = {"t": Schema.of(zone="int32", fare="int32")}
    findings, _ = query_type_findings(
        q, schemas, stats={"zone": (0, 15)}, total_rows=EXACT_BOUND
    )
    (f,) = [x for x in findings if x.rule == "T403"]
    assert f.severity is Severity.WARNING
    assert "2^24" in f.message
    # one row under the bound: provably exact, no finding
    findings, _ = query_type_findings(
        q, schemas, stats={"zone": (0, 15)}, total_rows=EXACT_BOUND - 1
    )
    assert [x for x in findings if x.rule == "T403"] == []


def test_t403_sum_bound_from_shard_stats():
    q = parse_sql("SELECT zone, SUM(fare) AS s FROM t GROUP BY zone")
    schemas = {"t": Schema.of(zone="int32", fare="int32")}
    findings, _ = query_type_findings(
        q, schemas, stats={"zone": (0, 15), "fare": (0, 100_000)},
        total_rows=1_000,
    )
    (f,) = [x for x in findings if x.rule == "T403"]
    assert "fare" in f.message and "sql line 1" in f.message
    assert f.hint
    # without stats the pass under-reports rather than guesses
    findings, _ = query_type_findings(q, schemas)
    assert findings == []


def test_t404_left_join_zero_fill_fires_for_key_and_aggregate():
    p = Pipeline("t404")
    p.sql(
        "agg",
        "SELECT z.borough, SUM(z.weight) AS w FROM trips AS t "
        "LEFT JOIN zones AS z ON t.zone = z.zone_id GROUP BY z.borough",
    )
    report = lint(p, JOINED)
    found = report.by_rule("T404")
    assert len(found) == 2
    assert all(f.severity is Severity.WARNING for f in found)
    assert "zero-fill" in found[0].message
    assert "zero-filled" in found[1].message
    assert all(f.hint for f in found)


def test_t404_inner_join_is_clean():
    p = Pipeline("t404_inner")
    p.sql(
        "agg",
        "SELECT z.borough, SUM(z.weight) AS w FROM trips AS t "
        "JOIN zones AS z ON t.zone = z.zone_id GROUP BY z.borough",
    )
    assert lint(p, JOINED).by_rule("T404") == []


def test_t404_unqualified_column_attributed_to_unique_owner():
    p = Pipeline("t404_plain")
    p.sql(
        "agg",
        "SELECT borough, COUNT(*) AS n FROM trips AS t "
        "LEFT JOIN zones AS z ON t.zone = z.zone_id GROUP BY borough",
    )
    (f,) = lint(p, JOINED).by_rule("T404")
    assert "'borough'" in f.message


# ------------------------------------------------- noqa on the node line
def test_noqa_rule_scoped_suppresses_t401():
    p = Pipeline("t401_noqa")
    p.sql("bad", "SELECT t.fare FROM trips AS t JOIN zones AS z ON t.score = z.zone_id")  # repro: noqa[T401]
    report = lint(p, JOINED)
    assert report.by_rule("T401") == []
    assert report.suppressed == 1


def test_noqa_bare_suppresses_t_rules():
    p = Pipeline("t401_noqa_bare")
    p.sql("bad", "SELECT t.fare FROM trips AS t JOIN zones AS z ON t.score = z.zone_id")  # repro: noqa
    report = lint(p, JOINED)
    assert report.by_rule("T401") == []
    assert report.suppressed == 1


def test_noqa_wrong_rule_does_not_suppress_t401():
    p = Pipeline("t401_noqa_wrong")
    p.sql("bad", "SELECT t.fare FROM trips AS t JOIN zones AS z ON t.score = z.zone_id")  # repro: noqa[T402]
    report = lint(p, JOINED)
    assert len(report.by_rule("T401")) == 1
    assert report.suppressed == 0


# ============================================================== C-rules
def test_c501_artifact_shadowing_a_lake_table():
    p = Pipeline("shadow")
    p.sql("orders", "SELECT pickup_at FROM taxi_table")
    findings, suppressed = run_concurrency_rules(p, catalog_tables={"orders"})
    (f,) = findings
    assert f.rule == "C501" and f.severity is Severity.WARNING
    assert "orders" in f.message and "shadows" in f.message
    assert f.hint and "rename" in f.hint
    assert suppressed == 0
    # no catalog context -> the rule cannot fire
    assert run_concurrency_rules(p)[0] == []


def test_noqa_c501_on_registration_line():
    p = Pipeline("shadow_noqa")
    p.sql("orders", "SELECT pickup_at FROM taxi_table")  # repro: noqa[C501]
    findings, suppressed = run_concurrency_rules(p, catalog_tables={"orders"})
    assert findings == [] and suppressed == 1


def test_c502_co_schedulable_writers_to_one_global():
    proj = Project("c502_pair")

    @proj.model()
    def first_writer(ctx, taxi_table):
        SHARED_LOG.append("first")
        return {"x": np.zeros(1, dtype=np.int32)}

    @proj.model()
    def second_writer(ctx, taxi_table):
        SHARED_LOG.append("second")
        return {"x": np.zeros(1, dtype=np.int32)}

    report = lint(proj.pipeline())
    (f,) = report.by_rule("C502")
    assert f.severity is Severity.WARNING
    assert "SHARED_LOG" in f.message
    assert "first_writer" in f.message and "second_writer" in f.message
    assert f.file and f.file.endswith("test_explain.py") and f.line
    assert "SHARED_LOG" in (f.snippet or "")
    assert f.hint and "artifact" in f.hint


def test_c502_dependency_path_orders_the_writes():
    proj = Project("c502_dep")

    @proj.model()
    def base_writer(ctx, taxi_table):
        SHARED_LOG.append("base")
        return {"x": np.zeros(1, dtype=np.int32)}

    @proj.model()
    def downstream_writer(ctx, base_writer):
        SHARED_LOG.append("down")
        return {"x": np.zeros(1, dtype=np.int32)}

    report = lint(proj.pipeline())
    assert report.by_rule("C502") == []
    assert report.by_rule("C503") == []


def test_c503_co_schedulable_writer_and_reader():
    proj = Project("c503")

    @proj.model()
    def totals_writer(ctx, taxi_table):
        TOTALS["rows"] = 1
        return {"x": np.zeros(1, dtype=np.int32)}

    @proj.model()
    def totals_reader(ctx, taxi_table):
        n = TOTALS.get("rows", 0)
        return {"x": np.full(1, n, dtype=np.int32)}

    report = lint(proj.pipeline())
    (f,) = report.by_rule("C503")
    assert "TOTALS" in f.message
    assert "totals_reader" in f.message and "totals_writer" in f.message
    assert report.by_rule("C502") == []  # only one side mutates


def test_noqa_suppresses_c502_at_the_write_site():
    proj = Project("c502_noqa")

    @proj.model()
    def muted_one(ctx, taxi_table):
        SHARED_LOG.append("a")  # repro: noqa[C502]
        return {"x": np.zeros(1, dtype=np.int32)}

    @proj.model()
    def muted_two(ctx, taxi_table):
        SHARED_LOG.append("b")  # repro: noqa[C502]
        return {"x": np.zeros(1, dtype=np.int32)}

    report = lint(proj.pipeline())
    assert report.by_rule("C502") == []
    assert report.suppressed >= 1


# ====================================================== client surface
@pytest.fixture
def client(tmp_path, rng):
    with repro.Client(tmp_path / "lake") as c:
        c.write_table("taxi_table", make_taxi_data(500, rng), schema=TAXI_SCHEMA)
        c.write_table(
            "orders",
            {
                "user_id": rng.integers(0, 50, 2000).astype(np.int32),
                "amount": rng.integers(0, 100, 2000).astype(np.int32),
                "famount": (rng.random(2000) * 100).astype(np.float32),
                "country": rng.integers(0, 20, 2000).astype(np.int32),
                "wid": rng.integers(0, 100_000, 2000).astype(np.int32),
            },
        )
        c.write_table(
            "big_orders_src",
            {
                "k": rng.integers(0, 10, 2000).astype(np.int32),
                "v": rng.integers(0, 2 ** 15, 2000).astype(np.int32),
            },
        )
        yield c


def test_explain_names_the_table_a_conjunct_is_pushed_into(tmp_path, rng):
    """TPC-H Q3: only lineitem, the FROM table, takes its conjunct into
    its scan; orders and customer are read whole, their conjuncts left
    in the residual for the device."""
    n = 300
    with repro.Client(tmp_path / "lake") as c:
        c.write_table("customer", {
            "c_custkey": np.arange(1, 31, dtype=np.int32),
            "c_mktsegment": rng.integers(0, 5, 30).astype(np.int8)})
        c.write_table("orders", {
            "o_orderkey": np.arange(1, 101, dtype=np.int32),
            "o_custkey": rng.integers(1, 31, 100).astype(np.int32),
            "o_orderdate": rng.integers(8000, 10000, 100).astype(np.int32),
            "o_shippriority": np.zeros(100, np.int32)})
        c.write_table("lineitem", {
            "l_orderkey": rng.integers(1, 101, n).astype(np.int32),
            "l_extendedprice": rng.random(n).astype(np.float32),
            "l_discount": rng.random(n).astype(np.float32),
            "l_shipdate": rng.integers(8000, 10000, n).astype(np.int32)})
        ex = c.explain(
            "SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue, "
            "o_orderdate, o_shippriority FROM lineitem "
            "JOIN orders ON l_orderkey = o_orderkey JOIN customer ON o_custkey = c_custkey "
            "WHERE c_mktsegment = 1 AND o_orderdate < '1995-03-15' "
            "AND l_shipdate > '1995-03-15' "
            "GROUP BY l_orderkey, o_orderdate, o_shippriority "
            "ORDER BY revenue DESC, o_orderdate LIMIT 10")
    text = ex.describe()
    pushed = [line.strip() for line in text.splitlines() if "pushdown" in line]
    assert pushed == ["pushdown  l_shipdate > 9204 (into the scan of lineitem)"]
    assert "c_mktsegment" in ex.residual and "o_orderdate" in ex.residual
    assert "(into the scan)" not in text


def test_explain_sql_kernel_verdict_with_plan(client):
    ex = client.explain(
        "SELECT country, SUM(amount) AS rev FROM orders "
        "WHERE amount > 10 GROUP BY country"
    )
    assert ex.engine_path == "kernel"
    assert ex.error is None
    assert ex.trace is not None and ex.trace.failed is None
    assert ex.pushdown and "amount" in ex.pushdown[0]
    assert ex.scans["orders"]["rows"] == 2000
    assert [n for n, _ in ex.output_schema] == ["country", "rev"]
    text = ex.describe()
    assert "route trace" in text and "execute   kernel" in text
    data = ex.to_json_dict()
    assert data["engine_path"] == "kernel" and data["trace"]["checks"]


def test_explain_sql_exactness_bail_carries_t403(client):
    ex = client.explain("SELECT k, SUM(v) AS s FROM big_orders_src GROUP BY k")
    assert ex.engine_path == "jnp"
    assert ex.trace.failed.check == "R208"
    assert any(f.rule == "T403" for f in ex.findings)


def test_client_lint_reaches_stats_grounded_t403(client):
    p = Pipeline("t403_lake")
    p.sql("sums", "SELECT k, SUM(v) AS s FROM big_orders_src GROUP BY k")
    assert "T403" in rules(client.lint(p))


def test_client_lint_c501_against_branch_head(client):
    p = Pipeline("shadow_lake")
    p.sql("orders", "SELECT pickup_at FROM taxi_table")
    (f,) = client.lint(p).by_rule("C501")
    assert "orders" in f.message


def test_explain_sql_predicted_route_error_matches_runtime(client):
    sql = "SELECT country, MIN(amount) AS m FROM orders GROUP BY country"
    ex = client.explain(sql, engine="kernel")
    assert ex.engine_path is None and ex.route is None
    assert ex.error is not None and "R" not in ex.error[:1]  # a message, not an id
    assert ex.trace is not None and ex.trace.failed.check == "R203"
    with pytest.raises(RouteError) as ei:
        client.query(sql, engine="kernel")
    assert str(ei.value) == ex.error  # byte-for-byte


AGREE_QUERIES = [
    # kernel-eligible: int agg, provable exactness, native filter
    "SELECT country, SUM(amount) AS rev FROM orders "
    "WHERE amount > 10 GROUP BY country",
    # plain scan — nothing to fuse
    "SELECT user_id, amount FROM orders WHERE amount > 80",
    # float aggregate: auto refuses, forced kernel runs (last-ulp drift)
    "SELECT country, SUM(famount) AS s FROM orders GROUP BY country",
    # two group keys — structurally ineligible
    "SELECT country, user_id, COUNT(*) AS n FROM orders "
    "GROUP BY country, user_id",
    # wide key range — exceeds the dense group axis
    "SELECT wid, COUNT(*) AS n FROM orders GROUP BY wid",
    # MIN — not kernel-fusable
    "SELECT country, MIN(amount) AS m FROM orders GROUP BY country",
]


@pytest.mark.parametrize("engine", ["auto", "jnp", "kernel"])
def test_explain_agrees_with_runtime_matrix(client, engine):
    for sql in AGREE_QUERIES:
        ex = client.explain(sql, engine=engine)
        if ex.error is not None:
            with pytest.raises(RouteError) as ei:
                client.query(sql, engine=engine)
            assert str(ei.value) == ex.error, sql
        else:
            client.query(sql, engine=engine)
            ran = [
                e for e in client.events()
                if type(e).__name__ == "QueryExecuted"
            ][-1].engine_path
            assert ex.engine_path == ran, (sql, engine)


def test_explain_unknown_table_positioned_sql_error(client):
    with pytest.raises(SqlError) as ei:
        client.explain("SELECT x FROM phantom")
    assert ei.value.pos == len("SELECT x FROM ")
    assert "phantom" in str(ei.value)


def _route_pipeline() -> Pipeline:
    p = Pipeline("routes")
    p.sql(
        "pickup_counts",
        "SELECT pickup_location_id, COUNT(*) AS n FROM taxi_table "
        "GROUP BY pickup_location_id",
    )
    p.sql("narrow", "SELECT pickup_at FROM taxi_table WHERE passenger_count > 2")
    p.sql("top", "SELECT n FROM pickup_counts")
    return p


def test_explain_pipeline_routes_equal_planner_stage_routes(client):
    p = _route_pipeline()
    pe = client.explain(p)
    snap = client.fmt.load_snapshot(client.catalog.table_key("taxi_table"))
    logical = build_logical_plan(p, external_schemas={"taxi_table": snap.schema})
    plan = build_physical_plan(
        logical, {"taxi_table": snap}, ctx=RunContext("main", 1, {})
    )
    planned = {}
    for stage in plan.stages:
        planned.update(stage.sql_routes)
    assert set(pe.routes) == {"pickup_counts", "narrow", "top"}
    assert pe.routes == planned  # RouteDecision equality, trace excluded


def test_explain_pipeline_node_details(client):
    pe = client.explain(_route_pipeline())
    assert pe.report.ok()
    by_name = {n.name: n for n in pe.nodes}
    counts = by_name["pickup_counts"]
    assert counts.route is not None and counts.trace.checks
    assert counts.output_schema is not None
    assert dict(counts.output_schema)["n"] == "int32"
    # node-sourced input: no shard stats, auto falls back to jnp at R205
    top = by_name["top"]
    assert top.route is None or top.route.engine_path == "jnp"
    text = pe.describe()
    assert "explain pipeline" in text and "route:" in text
    data = pe.to_json_dict()
    assert {n["name"] for n in data["nodes"]} == set(by_name)
    assert data["lint"]["errors"] == 0


def test_explain_pipeline_forced_kernel_surfaces_predicted_error(client):
    p = Pipeline("forced")
    p.sql("narrow", "SELECT pickup_at FROM taxi_table WHERE passenger_count > 2")
    pe = client.explain(p, engine="kernel")
    (node,) = [n for n in pe.nodes if n.name == "narrow"]
    assert node.route is None and node.error is not None
    assert "engine='kernel' forced" in node.error
    assert pe.routes == {}


def test_explain_pipeline_embeds_full_lint(client):
    p = Pipeline("broken")
    p.sql("trips", "SELECT total_fare FROM taxi_table")
    pe = client.explain(p)
    assert not pe.report.ok()
    assert pe.report.by_rule("L001")
    assert len(pe.nodes) == 1  # still explained as far as possible


def test_client_explain_zero_store_writes(client):
    puts_before = client.store.stats.puts
    ex = client.explain(
        "SELECT country, SUM(amount) AS rev FROM orders GROUP BY country"
    )
    assert ex.engine_path in ("kernel", "jnp")
    pe = client.explain(_route_pipeline())
    assert pe.nodes
    assert client.store.stats.puts == puts_before  # read-only plane
    assert client._executor is None  # no fleet was ever constructed


# -------------------------- LEFT JOIN zero-fill: inference vs execution
@pytest.mark.parametrize("kind", ["int32", "int8", "bool"])
def test_left_join_zero_fill_schema_matches_exec(tmp_path, rng, kind):
    n = 64
    if kind == "bool":
        left_keys = (np.arange(n) % 2).astype(bool)
        right_keys = np.array([True])
    else:
        left_keys = (np.arange(n) % 10).astype(kind)
        right_keys = np.arange(5).astype(kind)  # keys 5..9 unmatched
    with repro.Client(tmp_path / "lake") as c:
        c.write_table(
            "users",
            {"uid": left_keys, "score": np.arange(n, dtype=np.int32)},
        )
        c.write_table(
            "bonus",
            {
                "uid": right_keys,
                "extra": (np.arange(len(right_keys)) + 7).astype(np.int8),
            },
        )
        sql = (
            "SELECT u.score, b.extra FROM users AS u "
            "LEFT JOIN bonus AS b ON u.uid = b.uid"
        )
        ex = c.explain(sql)
        out = c.query(sql)
        # the statically-inferred schema IS the executed schema
        assert ex.output_schema is not None
        assert dict(ex.output_schema) == {
            name: str(arr.dtype) for name, arr in out.items()
        }
        # and unmatched left rows really are zero-filled, dtype preserved
        matched = np.isin(left_keys, right_keys)
        assert not matched.all()
        assert (out["extra"][~matched] == 0).all()


# ================================================== README rule catalog
def test_readme_rule_catalog_matches_generator():
    readme = (Path(__file__).resolve().parents[1] / "README.md").read_text()
    start = readme.index(CATALOG_BEGIN) + len(CATALOG_BEGIN)
    end = readme.index(CATALOG_END)
    assert readme[start:end].strip("\n") == rule_catalog_markdown()


def test_rule_catalog_covers_every_registry():
    text = rule_catalog_markdown()
    ids = [r.id for r in FUNCTION_RULES + TYPE_RULES + CONCURRENCY_RULES]
    ids += list(ROUTE_CHECKS)
    for rid in ids:
        assert f"`{rid}`" in text, rid


# ================================================================= CLI
PIPE_SRC = """
import repro

proj = repro.project("cli_explain_clean")
proj.sql("trips", "SELECT pickup_at FROM taxi_table WHERE passenger_count > 1")
"""


@pytest.fixture
def lake(tmp_path, rng):
    with repro.Client(tmp_path / "lake") as c:
        c.write_table("taxi_table", make_taxi_data(200, rng), schema=TAXI_SCHEMA)
    return tmp_path / "lake"


def test_cli_explain_sql(lake, capsys):
    main([
        "--lake", str(lake), "explain", "-q",
        "SELECT pickup_location_id, COUNT(*) AS n FROM taxi_table "
        "GROUP BY pickup_location_id",
    ])
    out = capsys.readouterr().out
    assert "route trace" in out and "execute" in out


def test_cli_explain_predicted_error_still_exits_zero(lake, capsys):
    # the predicted refusal IS the product — explain must not fail
    main([
        "--lake", str(lake), "explain", "--engine", "kernel", "-q",
        "SELECT pickup_location_id, MIN(passenger_count) AS m "
        "FROM taxi_table GROUP BY pickup_location_id",
    ])
    out = capsys.readouterr().out
    assert "REFUSED" in out and "fix:" in out


def test_cli_explain_pipeline(lake, tmp_path, capsys):
    f = tmp_path / "clean_pipe.py"
    f.write_text(PIPE_SRC)
    main(["--lake", str(lake), "explain", str(f)])
    out = capsys.readouterr().out
    assert "explain pipeline" in out and "trips" in out


def test_cli_explain_broken_pipeline_exits_nonzero(lake, capsys):
    with pytest.raises(SystemExit) as ei:
        main([
            "--lake", str(lake), "explain",
            "tests/fixtures/lint_broken_pipeline.py",
        ])
    assert ei.value.code == 1


def test_cli_explain_requires_exactly_one_target(lake, tmp_path):
    with pytest.raises(SystemExit) as ei:
        main(["--lake", str(lake), "explain"])
    assert "exactly one target" in str(ei.value.code)
    f = tmp_path / "clean_pipe.py"
    f.write_text(PIPE_SRC)
    with pytest.raises(SystemExit):
        main(["--lake", str(lake), "explain", str(f), "-q", "SELECT 1"])


def test_cli_explain_json_reports(lake, tmp_path, capsys):
    sql_json = tmp_path / "sql.json"
    main([
        "--lake", str(lake), "explain", "-q",
        "SELECT pickup_location_id, COUNT(*) AS n FROM taxi_table "
        "GROUP BY pickup_location_id",
        "--json", str(sql_json),
    ])
    data = json.loads(sql_json.read_text())
    assert data["engine_path"] in ("kernel", "jnp")
    assert data["trace"]["checks"]

    pipe_json = tmp_path / "pipe.json"
    f = tmp_path / "clean_pipe.py"
    f.write_text(PIPE_SRC)
    main(["--lake", str(lake), "explain", str(f), "--json", str(pipe_json)])
    data = json.loads(pipe_json.read_text())
    assert {n["name"] for n in data["nodes"]} == {"trips"}
    assert data["lint"]["errors"] == 0
