"""The ``tpch_sf1_joins`` configuration and its cell ``tpch_sf1_joins.q3``
at the CPU's tiny size (scale factor 0.01): the generator's key
structure, TPC-H Q3 through ``repro.Client`` against the reference, the
``QueryExecuted`` counters of the join path, and their readers."""
import json

import numpy as np
import pytest

import harness
import reference
import tiny

CELL = "tpch_sf1_joins.q3"
SEEDS = [3, 2**31 + 11]


@pytest.fixture(scope="module")
def cell():
    return tiny.cell(CELL)


@pytest.fixture(scope="module")
def tables(cell):
    return {seed: cell.generate(seed) for seed in SEEDS}


def _q3(cell):
    request = cell.mix["requests"][0]
    return request["sql"], request["statement"]


# ---------------------------------------------------------------- generator
@pytest.mark.parametrize("seed", SEEDS)
def test_keys_are_dbgens(tables, seed):
    t = tables[seed]
    custkey, orderkey = t["customer"]["c_custkey"], t["orders"]["o_orderkey"]
    assert np.array_equal(np.sort(custkey), np.arange(1, len(custkey) + 1))
    assert len(np.unique(orderkey)) == len(orderkey)
    assert np.all((orderkey - 1) % 32 < 8)  # the first 8 of every 32
    assert not np.any(t["orders"]["o_custkey"] % 3 == 0)
    assert np.isin(t["orders"]["o_custkey"], custkey).all()


@pytest.mark.parametrize("seed", SEEDS)
def test_lines_follow_their_orders(tables, seed):
    t = tables[seed]
    orders, lines = t["orders"], t["lineitem"]
    at = np.searchsorted(orders["o_orderkey"], lines["l_orderkey"])
    assert np.array_equal(orders["o_orderkey"][at], lines["l_orderkey"])
    delay = lines["l_shipdate"] - orders["o_orderdate"][at]
    assert delay.min() >= 1 and delay.max() <= 121
    assert np.all(lines["l_receiptdate"] - lines["l_shipdate"] >= 1)
    per_order = np.bincount(at, minlength=len(orders["o_orderkey"]))
    assert per_order.min() >= 1 and per_order.max() <= 7

    charge = (lines["l_extendedprice"].astype(np.float64) * (1 + lines["l_tax"].astype(np.float64))
              * (1 - lines["l_discount"].astype(np.float64)))
    total = np.bincount(at, weights=charge)
    np.testing.assert_allclose(orders["o_totalprice"], total, rtol=1e-6, atol=0.006)
    open_lines = np.bincount(at, weights=lines["l_linestatus"] == 1)
    status = np.where(open_lines == per_order, 1, np.where(open_lines == 0, 0, 2))
    assert np.array_equal(orders["o_orderstatus"], status)


def test_q3s_rows_are_the_same_for_every_seed(cell, tables):
    _, stmt = _q3(cell)
    a, b = ((reference.table_rows(stmt, tables[s]), reference.selected_rows(stmt, tables[s]))
            for s in SEEDS)
    assert a == b and all(a[0].values()) and a[1]
    sizes = [{t: reference.rows(v) for t, v in tables[s].items()} for s in SEEDS]
    assert sizes[0] == sizes[1]


def test_the_configuration_states_its_sf1_sizes():
    config = json.loads((harness.BENCH / "configs" / "tpch_sf1_joins.json").read_text())
    fixed = harness.load_cell(CELL).generator.structure(config)
    lines, orderdate, segment = fixed["lines"], fixed["orderdate"], fixed["segment"]
    rows = config["rows"]
    assert (len(segment), len(orderdate), int(lines.sum())) == \
        (rows["customer"], rows["orders"], rows["lineitem"])
    date = reference.days("1995-03-15")
    order_of = np.repeat(np.arange(len(orderdate)), lines)
    shipped = orderdate[order_of] + fixed["ship_delay"] > date
    joined = shipped & ((orderdate < date) & (segment[fixed["customer"]] == 1))[order_of]
    assert config["q3_rows"] == {"customer": int(np.sum(segment == 1)),
                                 "orders": int(np.sum(orderdate < date)),
                                 "lineitem": int(np.sum(shipped)),
                                 "joined": int(np.sum(joined)),
                                 "groups": len(np.unique(order_of[joined]))}


# ------------------------------------------------------ Q3 through the client
def _client(tmp_path, cell, tables):
    import repro

    client = repro.Client(tmp_path / "lake")
    for table, data in tables.items():
        client.write_table(table, data, schema=repro.Schema.of(
            **cell.config["tables"][table]["columns"]))
    return client


def _queries(client, sql, n=1):
    with client.events(follow=True) as sub:
        answers = [client.query(sql) for _ in range(n)]
        events = [e for e in sub.poll() if e.kind == "QueryExecuted"]
    return answers, events


@pytest.mark.parametrize("seed", SEEDS)
def test_q3_through_the_client_matches_the_reference(cell, tables, seed, tmp_path):
    sql, stmt = _q3(cell)
    with _client(tmp_path, cell, tables[seed]) as client:
        (got,), _ = _queries(client, sql)
    want = reference.run_statement(stmt, tables[seed])
    assert reference.rows(got) == 10 and reference.rows(want) > 10
    limit = cell.mix["limits"]["float_rel_err"]
    wrong, rel = reference.compare(got, want, stmt, limit)
    assert wrong == 0 and rel < limit, (wrong, rel)


def test_q3_counters(cell, tables, tmp_path):
    sql, stmt = _q3(cell)
    t = tables[SEEDS[0]]
    with _client(tmp_path, cell, t) as client:
        _, events = _queries(client, sql, n=3)
    # only lineitem's conjunct is pushed into a scan; orders and customer
    # are read whole and filtered on the device after the joins
    probe = reference.table_rows(stmt, t)["lineitem"]
    whole = {name: reference.rows(t[name]) for name in ("orders", "customer")}
    assert [e.rows_handed for e in events] == [probe + sum(whole.values())] * 3
    assert [e.rows_joined for e in events] == [reference.selected_rows(stmt, t)] * 3
    # two sorts of each build side; the group-by sorts by three keys and
    # validity, the ORDER BY by two keys and validity, at the probe's size
    sorts = 2 * whole["orders"] + 2 * whole["customer"] + (4 + 3) * probe
    assert [e.sorted_rows for e in events] == [sorts] * 3


def test_a_global_sum_sorts_nothing_and_joins_nothing(cell, tables, tmp_path):
    t = tables[SEEDS[0]]
    sql = ("SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem "
           "WHERE l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01' AND l_quantity < 24")
    with _client(tmp_path, cell, t) as client:
        _, events = _queries(client, sql, n=2)
    kept = reference.table_rows({"table": "lineitem", "where": [
        ["l_shipdate", ">=", "1994-01-01"], ["l_shipdate", "<", "1995-01-01"],
        ["l_quantity", "<", 24]]}, t)["lineitem"]
    assert [(e.rows_handed, e.rows_joined, e.sorted_rows) for e in events] == [(kept, 0, 0)] * 2


# ------------------------------------------------------------------ readers
class QueryExecuted:
    def __init__(self, **fields):
        self.__dict__.update(fields)


def _read(metric, events):
    return harness.load_module(harness.BENCH / "metrics" / f"{metric}.py").read(
        harness.Measured("query", 1.0, 0.0, 5.0, [], events))


@pytest.mark.parametrize("metric, field", [("handed_mrows.q3", "rows_handed"),
                                           ("sorted_mrows.q3", "sorted_rows")])
def test_readers_give_the_median_in_millions(metric, field):
    events = [QueryExecuted(**{field: n}) for n in (4_000_000, 5_000_000, 9_000_000)]
    assert _read(metric, events) == pytest.approx(5.0)
    assert _read(metric, [QueryExecuted(scan_s=0.1)]) is None


@pytest.mark.parametrize("metric", ["handed_mrows.q3", "sorted_mrows.q3"])
def test_readers_are_reported_in_their_cell_only(metric):
    for name in tiny.CELLS:
        traced = {m["name"] for m in harness.load_cell(name).metrics(True)}
        assert (metric in traced) == (name == CELL), name
