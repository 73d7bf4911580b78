"""TPC-H Q3, "Shipping Priority", through ``repro.Client`` on the CPU,
judged by the reference's joins and its comparison at a LIMIT.

Q3 (TPC-H 3.0.1, clause 2.4.3, at SEGMENT = BUILDING and DATE =
1995-03-15) joins ``lineitem``, ``orders`` and ``customer``, groups by
three keys, orders by revenue and keeps 10 rows.  The tables are built
here at scale factor 0.01 with TPC-H's key structure: unique
``c_custkey`` and ``o_orderkey`` (dbgen's first 8 of every 32 keys), no
orders for a customer whose key is a multiple of 3, 1-7 lines an order
and ``l_shipdate`` = ``o_orderdate`` + 1-121 days.  Market segments are
int8 codes in their sort order, as ``tpch_sf1`` codes its flags.

``FLOAT_REL_ERR`` = 1e-5.  A revenue is a float32 sum of at most 7
products ``l_extendedprice * (1 - l_discount)``: about 9 roundings of at
most 2**-24 each, 5.4e-7 at worst.  On 16 seeds at this size the
program read 4.2e-8 to 1.38e-7 and the bfloat16 control 3.8e-3 to
1.03e-2; the limit lies 72 times above the first and 380 below the
second.
"""
import datetime as dt

import numpy as np
import pytest

import harness
import reference

TPCH = harness.load_module(harness.BENCH / "configs" / "tpch_sf1.py")
EPOCH = dt.date(1970, 1, 1)
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
FLOAT_REL_ERR = 1e-5

SQL = ("SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue, "
       "o_orderdate, o_shippriority "
       "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
       "JOIN customer ON o_custkey = c_custkey "
       f"WHERE c_mktsegment = {SEGMENTS.index('BUILDING')} AND o_orderdate < '1995-03-15' "
       "AND l_shipdate > '1995-03-15' "
       "GROUP BY l_orderkey, o_orderdate, o_shippriority "
       "ORDER BY revenue DESC, o_orderdate LIMIT 10")
STATEMENT = {
    "table": "lineitem",
    "joins": [{"table": "orders", "on": ["l_orderkey", "o_orderkey"]},
              {"table": "customer", "on": ["o_custkey", "c_custkey"]}],
    "where": [["c_mktsegment", "=", SEGMENTS.index("BUILDING")],
              ["o_orderdate", "<", "1995-03-15"], ["l_shipdate", ">", "1995-03-15"]],
    "group_by": ["l_orderkey", "o_orderdate", "o_shippriority"],
    "aggs": [{"name": "revenue", "fn": "sum", "expr": "l_extendedprice * (1 - l_discount)"}],
    "order_by": [["revenue", "desc"], ["o_orderdate", "asc"]],
    "limit": 10,
}


def _day(iso: str) -> int:
    return (dt.date.fromisoformat(iso) - EPOCH).days


def _key(i: np.ndarray) -> np.ndarray:
    """dbgen's sparse keys: the first 8 of every 32."""
    return ((i // 8) * 32 + i % 8 + 1).astype(np.int32)


def q3_tables(seed: int, scale: float = 0.01):
    rng = np.random.default_rng(seed)
    customers, orders = int(150_000 * scale), int(1_500_000 * scale)
    custkey = np.arange(1, customers + 1)
    order = np.arange(orders)
    orderdate = rng.integers(_day("1992-01-01"), _day("1998-12-31") - 151 + 1, orders)
    line_of = np.repeat(order, rng.integers(1, 8, orders))
    n = len(line_of)
    partkey = rng.integers(1, int(200_000 * scale) + 1, n)
    quantity = rng.integers(1, 51, n)
    return {
        "customer": {"c_custkey": custkey.astype(np.int32),
                     "c_mktsegment": rng.integers(0, len(SEGMENTS), customers).astype(np.int8)},
        "orders": {"o_orderkey": _key(order),
                   "o_custkey": rng.choice(custkey[custkey % 3 != 0], orders).astype(np.int32),
                   "o_orderdate": orderdate.astype(np.int32),
                   "o_shippriority": np.zeros(orders, np.int32)},
        "lineitem": {"l_orderkey": _key(line_of),
                     "l_extendedprice": (quantity * TPCH.retail_price(partkey)).astype(np.float32),
                     "l_discount": (rng.integers(0, 11, n) / 100.0).astype(np.float32),
                     "l_shipdate": (orderdate[line_of] + rng.integers(1, 122, n)).astype(np.int32)},
    }


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_q3_through_the_client_matches_the_reference(seed, tmp_path):
    import repro

    tables = q3_tables(seed)
    with repro.Client(tmp_path / "lake") as client:
        for name, data in tables.items():
            client.write_table(name, data, schema=repro.Schema.of(
                **{c: str(a.dtype) for c, a in data.items()}))
        got = client.query(SQL)
    want = reference.run_statement(STATEMENT, tables)
    assert reference.rows(got) == 10 and reference.rows(want) > 90  # the cut matters
    wrong, rel = reference.compare(got, want, STATEMENT, FLOAT_REL_ERR)
    assert wrong == 0 and rel < FLOAT_REL_ERR, (wrong, rel)


@pytest.mark.parametrize("seed", [5, 6, 2**31 + 3])
def test_the_bf16_control_fails_the_limit(seed):
    tables = q3_tables(seed)
    want = reference.run_statement(STATEMENT, tables)
    low = reference.head(reference.run_statement(STATEMENT, tables, precision="bf16"), STATEMENT)
    _, rel = reference.compare(low, want, STATEMENT, FLOAT_REL_ERR)
    assert rel > 10 * FLOAT_REL_ERR, rel
