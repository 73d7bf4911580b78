"""Counters of the work a query hands the device, on ``QueryExecuted``.

The contract under test:

* ``rows_handed`` is the rows the statement's scans hand over, after the
  conjuncts pushed into them, summed over its tables;
* ``rows_joined`` is the rows that survive a statement's joins and its
  residual filter, returned by the program beside its answer, and 0
  without joins;
* ``sorted_rows`` is the elements the compiled program sorts, summed
  over its sort sites: the same on every call of one program, and 0 for
  a global sum, whose one-slot reorder XLA drops;
* the joins, filter, group-by and ORDER BY carry ``repro.*`` named
  scopes in the compiled program.
"""
import numpy as np

from repro.api import Client
from repro.engine.columnar import Columnar
from repro.engine.exec import compile_query, execute_query, sorted_rows
from repro.engine.sql import parse_sql
from repro.runtime import ExecutorConfig

DATE = 9204  # 1995-03-15
Q3 = ("SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue, "
      "o_orderdate, o_shippriority FROM lineitem "
      "JOIN orders ON l_orderkey = o_orderkey JOIN customer ON o_custkey = c_custkey "
      "WHERE c_mktsegment = 1 AND o_orderdate < '1995-03-15' "
      "AND l_shipdate > '1995-03-15' "
      "GROUP BY l_orderkey, o_orderdate, o_shippriority "
      "ORDER BY revenue DESC, o_orderdate LIMIT 10")


def _tables(seed: int = 3):
    rng = np.random.default_rng(seed)
    customers, orders, lines = 60, 400, 1500
    return {
        "customer": {"c_custkey": np.arange(1, customers + 1, dtype=np.int32),
                     "c_mktsegment": rng.integers(0, 5, customers).astype(np.int8)},
        "orders": {"o_orderkey": np.arange(1, orders + 1, dtype=np.int32),
                   "o_custkey": rng.integers(1, customers + 1, orders).astype(np.int32),
                   "o_orderdate": rng.integers(DATE - 400, DATE + 400, orders).astype(np.int32),
                   "o_shippriority": np.zeros(orders, np.int32)},
        "lineitem": {"l_orderkey": rng.integers(1, orders + 1, lines).astype(np.int32),
                     "l_extendedprice": rng.random(lines).astype(np.float32),
                     "l_discount": rng.random(lines).astype(np.float32),
                     "l_shipdate": rng.integers(DATE - 300, DATE + 300, lines).astype(np.int32)},
    }


def _events(tables, sql, calls):
    with Client.ephemeral(shard_rows=256,
                          executor_config=ExecutorConfig(max_workers=2)) as client:
        for name, data in tables.items():
            client.write_table(name, data)
        sub = client.events(follow=True)
        for _ in range(calls):
            client.query(sql)
        events = [e for e in sub.poll() if e.kind == "QueryExecuted"]
        sub.close()
    return events


def test_a_joined_query_counts_what_it_hands_over_joins_and_sorts():
    t = _tables()
    events = _events(t, Q3, calls=3)
    shipped = int(np.sum(t["lineitem"]["l_shipdate"] > DATE))
    orders, customers = len(t["orders"]["o_orderkey"]), len(t["customer"]["c_custkey"])
    # only the FROM table's conjunct is pushed into its scan
    assert [e.rows_handed for e in events] == [shipped + orders + customers] * 3

    o, c, li = t["orders"], t["customer"], t["lineitem"]
    order_ok = (o["o_orderdate"] < DATE) & (c["c_mktsegment"][o["o_custkey"] - 1] == 1)
    survived = (li["l_shipdate"] > DATE) & order_ok[li["l_orderkey"] - 1]
    assert [e.rows_joined for e in events] == [int(survived.sum())] * 3

    # two sorts of each build side; three group keys and validity, then
    # two ORDER BY keys and validity, at the probe side's capacity
    assert [e.sorted_rows for e in events] == [2 * orders + 2 * customers + 7 * shipped] * 3


def test_a_global_sum_hands_over_its_rows_and_sorts_nothing():
    t = _tables()
    sql = ("SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem "
           "WHERE l_shipdate >= '1995-01-01'")
    events = _events({"lineitem": t["lineitem"]}, sql, calls=2)
    kept = int(np.sum(t["lineitem"]["l_shipdate"] >= DATE - 73))
    assert [(e.rows_handed, e.rows_joined, e.sorted_rows) for e in events] == [(kept, 0, 0)] * 2


def test_the_program_returns_the_joined_rows_beside_the_answer():
    sql = ("SELECT k, SUM(v) AS s FROM t JOIN u ON k = uk WHERE w > 1 "
           "GROUP BY k ORDER BY s DESC LIMIT 3")
    query = parse_sql(sql)
    rel = Columnar.from_numpy({"k": np.arange(10, dtype=np.int32) % 4,
                               "v": np.ones(10, np.float32)})
    joined = {"u": Columnar.from_numpy({"uk": np.arange(3, dtype=np.int32),
                                        "w": np.array([0, 2, 3], np.int32)})}
    program = compile_query(query)
    answer, rows = program(rel, joined)
    assert int(rows) == 5  # k = 1 (three rows) and k = 2 (two rows)
    want = execute_query(query, rel, joined=joined).to_numpy()
    assert {k: v.tolist() for k, v in answer.to_numpy().items()} == \
        {k: v.tolist() for k, v in want.items()}
    assert sorted_rows(program, rel, joined) == 2 * 3 + 2 * 10 + 2 * 10

    text = program.lower(rel, joined).compile().as_text()
    for scope in ("repro.join", "repro.filter", "repro.group", "repro.order"):
        assert scope in text, scope
