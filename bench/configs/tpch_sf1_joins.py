"""Generator of the ``tpch_sf1_joins`` configuration: TPC-H ``customer``,
``orders`` and ``lineitem`` with dbgen's key structure, so that Q3 can
join them.

Values follow clause 4.2.3 of the TPC-H specification for every numeric
column of the three tables; strings with a fixed set of values are int8
codes in their sort order, ``o_clerk`` is the clerk's number, and the
free-text columns are left out (the engine holds no text).  The
``lineitem`` value rules (prices, suppliers, flag codes, dates) are
``tpch_sf1``'s, imported from its generator.

Keys as dbgen makes them: ``c_custkey`` 1..150,000 x SF; ``o_orderkey``
sparse, the first 8 of every 32 keys; ``o_custkey`` never a multiple of
3; 1-7 lines an order, each ``l_shipdate`` 1-121 days after its
``o_orderdate``.

The configuration's ``structure_seed`` fixes each order's date, its
number of lines, each line's ship delay, and the market segment of the
customer who placed it.  The run's seed permutes which key each order
and each customer gets (a customer with orders keeps a key that is not
a multiple of 3) and the rows' storage order, and draws every other
value.  So each table's rows after its own Q3 conjunct
(``c_mktsegment``, ``o_orderdate``, ``l_shipdate``), and the rows that
survive Q3's joins, are as many for every seed: every seed hands the
device the same shapes and the same work, and only the values differ.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, Mapping

import numpy as np

import harness

TPCH = harness.load_module(Path(__file__).with_name("tpch_sf1.py"))

#: dictionary codes, in the strings' sort order
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
ORDERSTATUS = {"F": 0, "O": 1, "P": 2}
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
NATIONS = 25


def counts(config: Mapping) -> Dict[str, int]:
    """Rows of ``customer`` and ``orders``, and the parts, suppliers and
    clerks the values are drawn from, at the configuration's scale."""
    sf = float(config["scale_factor"])
    return {name: int(round(float(config[f"{name}_per_sf"]) * sf))
            for name in ("customers", "orders", "parts", "suppliers", "clerks")}


def structure(config: Mapping) -> Dict[str, np.ndarray]:
    """What ``structure_seed`` fixes: each order's date (days since
    1970-01-01), number of lines and customer, each line's ship delay
    (the lines of an order in turn), and each customer's market segment.
    Customers are numbered with those who place orders first: the
    ``customers - customers // 3`` whose keys are not multiples of 3."""
    n = counts(config)
    fixed = np.random.default_rng(config["structure_seed"])
    start, end = TPCH._day(config["start_date"]), TPCH._day(config["end_date"])
    orderdate = fixed.integers(start, end - 151 + 1, n["orders"])
    lines = fixed.integers(1, 8, n["orders"])
    return {
        "orderdate": orderdate,
        "lines": lines,
        "ship_delay": fixed.integers(1, 122, int(lines.sum())),
        "customer": fixed.integers(0, n["customers"] - n["customers"] // 3, n["orders"]),
        "segment": fixed.integers(0, len(SEGMENTS), n["customers"]),
    }


def _key(i: np.ndarray) -> np.ndarray:
    """dbgen's sparse order keys: the first 8 of every 32."""
    return (i // 8) * 32 + i % 8 + 1


def generate(config: Mapping, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The three tables of ``config``, drawn from ``seed``."""
    n = counts(config)
    fixed = structure(config)
    orderdate, lines = fixed["orderdate"], fixed["lines"]
    current = TPCH._day(config["current_date"])
    rng = np.random.default_rng(seed)

    # customer: each gets a key of its class, rows in the seed's order
    keys = np.arange(1, n["customers"] + 1)
    custkey = np.concatenate([rng.permutation(keys[keys % 3 != 0]),
                              rng.permutation(keys[keys % 3 == 0])])
    stored = rng.permutation(n["customers"])
    customer = {
        "c_custkey": custkey[stored],
        "c_nationkey": rng.integers(0, NATIONS, n["customers"]),
        "c_acctbal": rng.integers(-99999, 1000000, n["customers"]) / 100.0,
        "c_mktsegment": fixed["segment"][stored],
    }

    # lineitem, the lines of each order in turn (structure order)
    order_of = np.repeat(np.arange(n["orders"]), lines)
    m = len(order_of)
    first = np.cumsum(lines) - lines
    shipdate = orderdate[order_of] + fixed["ship_delay"]
    receiptdate = shipdate + rng.integers(1, 31, m)
    partkey = rng.integers(1, n["parts"] + 1, m)
    quantity = rng.integers(1, 51, m)
    price = (quantity * TPCH.retail_price(partkey)).astype(np.float32)
    discount = (rng.integers(0, 11, m) / 100.0).astype(np.float32)
    tax = (rng.integers(0, 9, m) / 100.0).astype(np.float32)
    returned = rng.integers(0, 2, m).astype(bool)
    flags = TPCH.RETURNFLAG
    linestatus = np.where(shipdate > current, TPCH.LINESTATUS["O"], TPCH.LINESTATUS["F"])

    # orders: order perm[r] is stored at row r with the r-th key, as
    # dbgen stores orders by key; its status and total price from its lines
    perm = rng.permutation(n["orders"])
    key_of = np.empty(n["orders"], np.int64)
    key_of[perm] = _key(np.arange(n["orders"]))
    open_lines = np.add.reduceat((linestatus == TPCH.LINESTATUS["O"]).astype(np.int64), first)
    status = np.where(open_lines == lines, ORDERSTATUS["O"],
                      np.where(open_lines == 0, ORDERSTATUS["F"], ORDERSTATUS["P"]))
    charge = price.astype(np.float64) * (1 + tax.astype(np.float64)) * (1 - discount.astype(np.float64))
    total = np.round(np.add.reduceat(charge, first), 2)
    orders = {
        "o_orderkey": key_of[perm],
        "o_custkey": custkey[fixed["customer"]][perm],
        "o_orderstatus": status[perm],
        "o_totalprice": total[perm],
        "o_orderdate": orderdate[perm],
        "o_orderpriority": rng.integers(0, len(PRIORITIES), n["orders"]),
        "o_clerk": rng.integers(1, n["clerks"] + 1, n["orders"]),
        "o_shippriority": np.zeros(n["orders"], np.int64),
    }

    lineitem = {
        "l_orderkey": key_of[order_of],
        "l_partkey": partkey,
        "l_suppkey": TPCH.supplier(partkey, rng.integers(0, 4, m), n["suppliers"]),
        "l_linenumber": np.arange(m) - first[order_of] + 1,
        "l_quantity": quantity,
        "l_extendedprice": price,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": np.where(receiptdate <= current,
                                 np.where(returned, flags["R"], flags["A"]), flags["N"]),
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": orderdate[order_of] + rng.integers(30, 91, m),
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, len(TPCH.SHIPINSTRUCT), m),
        "l_shipmode": rng.integers(0, len(TPCH.SHIPMODE), m),
    }
    stored = rng.permutation(m)
    lineitem = {c: v[stored] for c, v in lineitem.items()}

    tables = {"customer": customer, "orders": orders, "lineitem": lineitem}
    return {
        name: {c: tables[name][c].astype(dtype) for c, dtype in spec["columns"].items()}
        for name, spec in config["tables"].items()
    }
