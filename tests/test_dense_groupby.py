"""The dense group-by: grouping over a static slot axis when shard
statistics bound every group key.

The contract under test:

* routing — ``plan_route`` gives a jnp decision a ``group_domain`` when
  integer statistics bound every key (left-join widening included) within
  ``DENSE_MAX_GROUPS`` slots, and none otherwise; ``engine="jnp"`` and the
  kernel's own queries carry none;
* results — the dense path's compacted output equals the sort path's
  (``route=None``): integer, key and min/max columns byte for byte, float
  sums and means within 1e-5 relative;
* the cache — a dense route that sums or averages may add floats in
  another order than the reference, and says so (``reassociates``), so
  the node's fingerprint names its group path;
* the counter — ``QueryExecuted.group_path`` says which group-by ran.
"""
import numpy as np
import pytest

from repro.api import Client
from repro.engine.columnar import Columnar
from repro.engine.exec import execute_query, group_path
from repro.engine.route import DENSE_MAX_GROUPS, plan_route, reassociates
from repro.engine.sql import parse_sql
from repro.runtime import ExecutorConfig

N = 4000
#: the domain the statistics claim for k0, k1, k2 (k2 is bool)
STATS = {"k0": (-3, 4), "k1": (10, 15), "k2": (0, 1), "v": (-50, 50)}

AGGS = (
    "COUNT(*) AS n, SUM(v) AS sv, SUM(f) AS sf, AVG(v) AS av, AVG(f) AS af, "
    "MIN(v) AS mnv, MAX(v) AS mxv, MIN(f) AS mnf, MAX(f) AS mxf, "
    "SUM(f * 2 + v) AS se"
)
#: output columns compared byte for byte; the rest are float sums/means
EXACT = {"k0", "k1", "k2", "n", "sv", "mnv", "mxv", "mnf", "mxf"}


def _relation(seed: int, *, edges: bool = True, absent: bool = False) -> Columnar:
    """Random rows with about a fifth invalid.  ``edges`` puts keys on
    both ends of the claimed domain; ``absent`` leaves whole key values
    (hence slots) out."""
    rng = np.random.default_rng(seed)
    lo0, hi0 = STATS["k0"]
    k0 = rng.integers(lo0, hi0 + 1, N)
    if absent:
        k0 = np.where(np.isin(k0, (-2, 0, 3)), 1, k0)
    if edges:
        k0[:2] = (lo0, hi0)
    lo1, hi1 = STATS["k1"]
    k1 = rng.integers(lo1 + (1 if absent else 0), hi1 + 1, N)
    rel = Columnar.from_numpy({
        "k0": k0.astype(np.int8),
        "k1": k1.astype(np.int32),
        "k2": rng.random(N) < 0.3,
        "v": rng.integers(-50, 51, N).astype(np.int32),
        "f": (rng.random(N) * 100 - 20).astype(np.float32),
    })
    valid = rng.random(N) > 0.2
    valid[:2] = True  # the edge keys stay live
    return rel.mask_where(np.asarray(valid))


def _dense_route(sql: str):
    query = parse_sql(sql)
    route = plan_route(query, stats=STATS, total_rows=N)
    assert route.engine_path == "jnp" and route.group_domain is not None
    assert group_path(query, route) == "dense"
    return query, route


def _assert_same(dense, ref):
    assert list(dense) == list(ref)
    for name in ref:
        assert dense[name].dtype == ref[name].dtype, name
        if name in EXACT:
            np.testing.assert_array_equal(dense[name], ref[name], err_msg=name)
        else:
            np.testing.assert_allclose(dense[name], ref[name], rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("sql, kwargs", [
    (f"SELECT {AGGS} FROM t", {}),
    (f"SELECT k0, {AGGS} FROM t GROUP BY k0", {}),
    (f"SELECT k0, k1, {AGGS} FROM t GROUP BY k0, k1", {}),
    (f"SELECT k1, k0, k2, {AGGS} FROM t GROUP BY k1, k0, k2", {}),
    (f"SELECT k0, k1, {AGGS} FROM t GROUP BY k0, k1", {"absent": True}),
    (f"SELECT k0, k1, {AGGS} FROM t WHERE f > 10 GROUP BY k0, k1", {}),
    (f"SELECT k0, k1, {AGGS} FROM t GROUP BY k0, k1 ORDER BY k1 DESC, k0", {}),
    (f"SELECT k0, k2, {AGGS} FROM t GROUP BY k0, k2 ORDER BY n DESC LIMIT 5", {}),
    ("SELECT k0, k1 FROM t GROUP BY k0, k1 ORDER BY k0 LIMIT 7", {"absent": True}),
], ids=["0-keys", "1-key", "2-keys", "3-keys", "absent-groups", "where",
        "order-by", "order-by-limit", "bare-group-by"])
def test_dense_equals_the_sort_path(sql, kwargs):
    query, route = _dense_route(sql)
    for seed in (0, 1):
        rel = _relation(seed, **kwargs)
        dense = execute_query(query, rel, route=route)
        assert dense.capacity <= route.dense_groups
        _assert_same(dense.to_numpy(), execute_query(query, rel).to_numpy())


def test_dense_keys_on_the_domain_edges_and_absent_slots():
    query, route = _dense_route(f"SELECT k0, {AGGS} FROM t GROUP BY k0")
    rel = _relation(3, absent=True)
    out = execute_query(query, rel, route=route)
    got = out.to_numpy()
    # -3 and 4 are the domain's edges; -2, 0 and 3 never occur
    assert list(got["k0"]) == [-3, -1, 1, 2, 4]
    # absent slots are zeroed and trail the present ones
    assert out.capacity == 8
    assert not np.asarray(out.valid)[5:].any()
    assert not np.asarray(out.columns["mnv"])[5:].any()


def test_keys_outside_the_domain_belong_to_no_slot():
    """A key past the statistics' bounds joins no group, and never
    aliases into another slot of a multi-key domain."""
    query, route = _dense_route(f"SELECT k0, k1, {AGGS} FROM t GROUP BY k0, k1")
    rel = _relation(5)
    k1 = np.asarray(rel.columns["k1"]).copy()
    k1[::7] = STATS["k1"][1] + 1  # one past the domain's last slot
    rel = rel.with_columns({"k1": np.asarray(k1)})
    dense = execute_query(query, rel, route=route).to_numpy()
    inside = rel.mask_where(np.asarray(k1 <= STATS["k1"][1]))
    _assert_same(dense, execute_query(query, inside).to_numpy())


def test_dense_left_join_groups_zero_filled_misses():
    """Rows with no match in the left-joined table group under 0, which
    the widened domain admits (their key's statistics are 5..7)."""
    sql = ("SELECT z.b, COUNT(*) AS n, MIN(t.v) AS m FROM t "
           "LEFT JOIN z ON t.k0 = z.id GROUP BY z.b")
    query = parse_sql(sql)
    route = plan_route(query, stats={"z.b": (5, 7), "b": (5, 7)}, total_rows=N)
    assert route.group_domain == ((0, 8),)
    rel = _relation(4)
    zones = Columnar.from_numpy({
        "id": np.arange(0, 4, dtype=np.int32),  # k0 in -3..-1 misses
        "b": np.array([5, 7, 5, 6], np.int32),
    })
    dense = execute_query(query, rel, joined={"z": zones}, route=route).to_numpy()
    ref = execute_query(query, rel, joined={"z": zones}).to_numpy()
    assert 0 in dense["b"]
    for name in ref:
        np.testing.assert_array_equal(dense[name], ref[name])


# ------------------------------------------------------------------ routing
@pytest.mark.parametrize("sql, stats, want", [
    # two keys with stats: bails at R202, carries the domain
    ("SELECT a, b, COUNT(*) AS n FROM t GROUP BY a, b",
     {"a": (0, 2), "b": (0, 1)}, ((0, 3), (0, 2))),
    # a global aggregation is one slot
    ("SELECT SUM(x) AS s FROM t", {}, ()),
    # MIN bails at R203; a float value column at R208
    ("SELECT a, MIN(x) AS m FROM t GROUP BY a", {"a": (-4, 4)}, ((-4, 9),)),
    ("SELECT a, SUM(x) AS s FROM t GROUP BY a", {"a": (1, 3)}, ((1, 3),)),
    # a key without integer stats (node-sourced input, float key)
    ("SELECT a, b, COUNT(*) AS n FROM t GROUP BY a, b", {"a": (0, 2)}, None),
    # more slots than DENSE_MAX_GROUPS
    ("SELECT a, b, COUNT(*) AS n FROM t GROUP BY a, b",
     {"a": (0, DENSE_MAX_GROUPS // 2), "b": (0, 1)}, None),
    # exactly DENSE_MAX_GROUPS
    ("SELECT a, b, COUNT(*) AS n FROM t GROUP BY a, b",
     {"a": (1, DENSE_MAX_GROUPS // 2), "b": (0, 1)},
     ((1, DENSE_MAX_GROUPS // 2), (0, 2))),
    # the left-joined key's range widens to 0, the FROM table's does not
    ("SELECT t.a, z.b, COUNT(*) AS n FROM t LEFT JOIN z ON t.k = z.id "
     "GROUP BY t.a, z.b", {"t.a": (2, 3), "z.b": (5, 6)}, ((2, 2), (0, 7))),
    # not an aggregation
    ("SELECT a FROM t WHERE a > 1", {"a": (0, 2)}, None),
], ids=["two-keys", "global", "min", "float-sum", "key-without-stats",
        "too-many-slots", "at-the-cap", "left-join", "no-aggregation"])
def test_route_group_domain(sql, stats, want):
    route = plan_route(parse_sql(sql), stats=stats, total_rows=1000)
    assert route.engine_path == "jnp"
    assert route.group_domain == want
    assert route.to_json_dict()["group_domain"] == (
        None if want is None else [list(d) for d in want])
    # the domain is no check: the trace still ends at the one that bailed
    assert route.trace.checks[-1] is route.trace.failed


def test_engine_jnp_pins_the_sort_path():
    query = parse_sql("SELECT a, b, COUNT(*) AS n FROM t GROUP BY a, b")
    route = plan_route(query, engine="jnp", stats={"a": (0, 2), "b": (0, 1)})
    assert route.group_domain is None
    assert group_path(query, route) == "sort"
    assert group_path(query, None) == "sort"


def test_the_kernel_still_takes_single_key_count_and_sum():
    query = parse_sql("SELECT a, COUNT(*) AS n, SUM(x) AS s FROM t GROUP BY a")
    route = plan_route(query, stats={"a": (0, 9), "x": (0, 10)}, total_rows=1000)
    assert route.engine_path == "kernel" and route.group_domain is None
    assert group_path(query, route) == "kernel"
    assert group_path(parse_sql("SELECT a FROM t"), route) == ""


@pytest.mark.parametrize("sql, engine, want", [
    ("SELECT a, b, SUM(x) AS s FROM t GROUP BY a, b", "auto", True),
    ("SELECT a, b, AVG(x) AS m FROM t GROUP BY a, b", "auto", True),
    ("SELECT a, b, COUNT(*) AS n, MIN(x) AS lo, MAX(x) AS hi FROM t "
     "GROUP BY a, b", "auto", False),
    ("SELECT a, b, SUM(x) AS s FROM t GROUP BY a, b", "jnp", False),
    ("SELECT a, SUM(x) AS s FROM t GROUP BY a", "kernel", False),
], ids=["dense-sum", "dense-avg", "dense-count-min-max", "sort", "kernel"])
def test_reassociates_only_where_a_dense_route_sums(sql, engine, want):
    query = parse_sql(sql)
    route = plan_route(
        query, engine=engine, stats={"a": (0, 2), "b": (0, 1), "x": (0, 9)},
        total_rows=1000,
    )
    assert reassociates(query, route) is want


def test_the_domain_keys_the_compiled_program():
    query = parse_sql("SELECT a, b, COUNT(*) AS n FROM t GROUP BY a, b")
    small = plan_route(query, stats={"a": (0, 2), "b": (0, 1)})
    wide = plan_route(query, stats={"a": (0, 3), "b": (0, 1)})
    assert small != wide and hash(small) != hash(wide)


# ------------------------------------------------------------ Client.query
Q1 = (
    "SELECT l_returnflag, l_linestatus, SUM(l_quantity) AS sum_qty, "
    "SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
    "AVG(l_discount) AS avg_disc, COUNT(*) AS count_order FROM lineitem "
    "WHERE l_shipdate <= 10400 GROUP BY l_returnflag, l_linestatus "
    "ORDER BY l_returnflag, l_linestatus"
)


def test_client_query_publishes_the_dense_path():
    rng = np.random.default_rng(11)
    n = 5000
    data = {
        "l_returnflag": rng.integers(0, 3, n).astype(np.int8),
        "l_linestatus": rng.integers(0, 2, n).astype(np.int8),
        "l_quantity": rng.integers(1, 51, n).astype(np.int32),
        "l_extendedprice": (rng.random(n) * 1e4).astype(np.float32),
        "l_discount": (rng.integers(0, 11, n) / 100).astype(np.float32),
        "l_shipdate": rng.integers(8000, 10600, n).astype(np.int32),
    }
    with Client.ephemeral(
        shard_rows=1024, executor_config=ExecutorConfig(max_workers=2)
    ) as client:
        client.write_table("lineitem", data)
        assert "dense, G=6" in client.explain(Q1).describe()
        assert "sort (engine='jnp'" in client.explain(Q1, engine="jnp").describe()
        sub = client.events(follow=True)
        got = client.query(Q1)
        events = [e for e in sub.poll() if e.kind == "QueryExecuted"]
        sub.close()
    assert [e.group_path for e in events] == ["dense"]

    keep = data["l_shipdate"] <= 10400
    groups = sorted({(int(a), int(b)) for a, b in zip(
        data["l_returnflag"][keep], data["l_linestatus"][keep])})
    assert list(zip(got["l_returnflag"], got["l_linestatus"])) == groups
    for i, (a, b) in enumerate(groups):
        sel = keep & (data["l_returnflag"] == a) & (data["l_linestatus"] == b)
        assert got["count_order"][i] == sel.sum()
        assert got["sum_qty"][i] == data["l_quantity"][sel].sum()
        price = data["l_extendedprice"][sel].astype(np.float64)
        disc = data["l_discount"][sel].astype(np.float64)
        np.testing.assert_allclose(
            got["sum_disc_price"][i], (price * (1 - disc)).sum(), rtol=1e-5)
        np.testing.assert_allclose(got["avg_disc"][i], disc.mean(), rtol=1e-5)
