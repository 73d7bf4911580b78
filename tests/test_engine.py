"""Query engine: operators vs numpy oracles, SQL front-end, jit stability."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.engine import Columnar, Query, col, compile_query, execute_query, parse_sql


def make_rel(n, rng):
    return Columnar.from_numpy(
        {
            "loc": rng.integers(0, 16, n).astype(np.int32),
            "dst": rng.integers(0, 8, n).astype(np.int32),
            "count": rng.integers(0, 10, n).astype(np.int32),
            "fare": (rng.random(n) * 50).astype(np.float32),
        }
    )


def test_filter_project(rng):
    rel = make_rel(100, rng)
    q = Query("t").where(col("count") > 4).select("fare", double=col("fare") * 2)
    out = execute_query(q, rel).to_numpy()
    fare = np.asarray(rel.columns["fare"])
    cnt = np.asarray(rel.columns["count"])
    np.testing.assert_allclose(out["fare"], fare[cnt > 4], rtol=1e-6)
    np.testing.assert_allclose(out["double"], 2 * fare[cnt > 4], rtol=1e-6)


def test_groupby_sum_count_vs_numpy(rng):
    rel = make_rel(500, rng)
    q = (
        Query("t")
        .group_by("loc")
        .agg("sum", col("fare"), "fare_sum")
        .count("n")
    )
    out = execute_query(q, rel).to_numpy()
    loc = np.asarray(rel.columns["loc"])
    fare = np.asarray(rel.columns["fare"])
    order = np.argsort(out["loc"])
    for k in ("loc", "fare_sum", "n"):
        out[k] = out[k][order]
    expected_keys = np.unique(loc)
    np.testing.assert_array_equal(out["loc"], expected_keys)
    for i, key in enumerate(expected_keys):
        np.testing.assert_allclose(out["fare_sum"][i], fare[loc == key].sum(), rtol=1e-5)
        assert out["n"][i] == (loc == key).sum()


def test_groupby_multikey_min_max_mean(rng):
    rel = make_rel(400, rng)
    q = (
        Query("t")
        .group_by("loc", "dst")
        .agg("min", col("fare"), "lo")
        .agg("max", col("fare"), "hi")
        .agg("mean", col("fare"), "avg")
    )
    out = execute_query(q, rel).to_numpy()
    loc = np.asarray(rel.columns["loc"])
    dst = np.asarray(rel.columns["dst"])
    fare = np.asarray(rel.columns["fare"])
    assert len(out["loc"]) == len(np.unique(loc * 8 + dst))
    for i in range(len(out["loc"])):
        m = (loc == out["loc"][i]) & (dst == out["dst"][i])
        np.testing.assert_allclose(out["lo"][i], fare[m].min(), rtol=1e-6)
        np.testing.assert_allclose(out["hi"][i], fare[m].max(), rtol=1e-6)
        np.testing.assert_allclose(out["avg"][i], fare[m].mean(), rtol=1e-5)


def test_sort_desc_and_limit(rng):
    rel = make_rel(64, rng)
    q = Query("t").select("fare").sort("fare", desc=True).take(10)
    out = execute_query(q, rel).to_numpy()
    fare = np.sort(np.asarray(rel.columns["fare"]))[::-1][:10]
    np.testing.assert_allclose(out["fare"], fare, rtol=1e-6)


def test_filter_then_groupby_pipeline(rng):
    """The paper's fused shape: WHERE + GROUP BY + ORDER BY in one program."""
    rel = make_rel(1000, rng)
    q = (
        Query("t")
        .where(col("count") > 2)
        .group_by("loc")
        .count("counts")
        .sort("counts", desc=True)
    )
    out = execute_query(q, rel).to_numpy()
    loc = np.asarray(rel.columns["loc"])
    cnt = np.asarray(rel.columns["count"])
    kept = loc[cnt > 2]
    keys, counts = np.unique(kept, return_counts=True)
    order = np.argsort(-counts, kind="stable")
    np.testing.assert_array_equal(np.sort(out["counts"])[::-1], out["counts"])
    np.testing.assert_array_equal(np.sort(out["counts"]), np.sort(counts))
    # counts per key must match exactly
    d = dict(zip(out["loc"].tolist(), out["counts"].tolist()))
    assert d == dict(zip(keys.tolist(), counts.tolist()))


def test_jit_compile_query_matches_eager(rng):
    rel = make_rel(256, rng)
    q = Query("t").where(col("fare") < 25.0).group_by("dst").agg("sum", col("fare"), "s")
    eager = execute_query(q, rel).to_numpy()
    compiled = compile_query(q)
    jitted = compiled(rel).to_numpy()
    for k in eager:
        np.testing.assert_allclose(eager[k], jitted[k], rtol=1e-6)
    # cache hit returns the same callable (warm container analogy)
    assert compile_query(q) is compiled


def test_empty_and_all_filtered(rng):
    rel = make_rel(32, rng)
    q = Query("t").where(col("fare") < -1.0).group_by("loc").count("n")
    out = execute_query(q, rel).to_numpy()
    assert len(out["n"]) == 0


# ------------------------------------------------------------------ SQL
def test_sql_paper_step1():
    q = parse_sql(
        """
        SELECT
         pickup_location_id,
         passenger_count as count,
         dropoff_location_id
        FROM
         taxi_table
        WHERE
         pickup_at >= '2019-04-01'
        """
    )
    assert q.source == "taxi_table"
    assert [a for a, _ in q.projections] == [
        "pickup_location_id", "count", "dropoff_location_id",
    ]
    pushed, residual = q.filter_expr.as_pushdown_conjuncts()
    assert residual is None
    assert pushed[0].column == "pickup_at" and pushed[0].op == ">="
    assert pushed[0].value == float((np.datetime64("2019-04-01") - np.datetime64("1970-01-01")) / np.timedelta64(1, "D"))


def test_sql_paper_step3():
    q = parse_sql(
        """
        SELECT
         pickup_location_id,
         dropoff_location_id,
         COUNT(*) AS counts
        FROM
         trips
        GROUP BY
         pickup_location_id,
         dropoff_location_id
        ORDER BY
         counts DESC
        """
    )
    assert q.source == "trips"
    assert q.group_keys == ("pickup_location_id", "dropoff_location_id")
    assert q.aggregates[0].fn == "count" and q.aggregates[0].name == "counts"
    assert q.order_by == (("counts", True),)


def test_sql_execution_end_to_end(rng):
    rel = make_rel(300, rng)
    q = parse_sql("SELECT loc, SUM(fare) AS total FROM t WHERE count > 3 GROUP BY loc ORDER BY total DESC LIMIT 5")
    out = execute_query(q, rel).to_numpy()
    loc = np.asarray(rel.columns["loc"])
    cnt = np.asarray(rel.columns["count"])
    fare = np.asarray(rel.columns["fare"])
    mask = cnt > 3
    totals = {k: fare[mask & (loc == k)].sum() for k in np.unique(loc[mask])}
    expect = sorted(totals.values(), reverse=True)[:5]
    np.testing.assert_allclose(out["total"], expect, rtol=1e-5)


def test_sql_errors():
    with pytest.raises(SyntaxError):
        parse_sql("SELECT a FROM")
    with pytest.raises(SyntaxError):
        parse_sql("SELECT a, SUM(b) AS s FROM t")  # bare col with agg, no GROUP BY


@given(
    n=st.integers(1, 300),
    threshold=st.floats(0, 50, allow_nan=False),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_property_masked_filter_equals_compact_numpy(n, threshold, seed):
    rng = np.random.default_rng(seed)
    rel = make_rel(n, rng)
    q = Query("t").where(col("fare") >= threshold).select("fare")
    out = execute_query(q, rel).to_numpy()
    fare = np.asarray(rel.columns["fare"])
    np.testing.assert_allclose(out["fare"], fare[fare >= threshold], rtol=1e-6)


@given(
    n=st.integers(1, 200),
    nkeys=st.integers(1, 30),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=25, deadline=None)
def test_property_groupby_sum_invariant(n, nkeys, seed):
    """Sum of per-group sums == global sum of filtered values."""
    rng = np.random.default_rng(seed)
    rel = Columnar.from_numpy(
        {
            "k": rng.integers(0, nkeys, n).astype(np.int32),
            "v": rng.standard_normal(n).astype(np.float32),
        }
    )
    q = Query("t").group_by("k").agg("sum", col("v"), "s").count("n")
    out = execute_query(q, rel).to_numpy()
    np.testing.assert_allclose(
        out["s"].sum(), np.asarray(rel.columns["v"]).sum(), rtol=2e-4, atol=1e-4
    )
    assert out["n"].sum() == n
