"""Generator of the ``tpch_sf1`` configuration: TPC-H ``lineitem``.

Values follow clause 4.2.3 of the TPC-H specification for every column
but ``l_comment`` (the engine holds no text); strings with a fixed set of
values are int8 codes in their sort order.  The multiset of
(l_shipdate, l_discount, l_quantity) comes from the configuration's
fixed ``structure_seed`` and the run's seed only permutes it, so every
statement's scan hands the device the same number of rows whatever the
seed.
"""
from __future__ import annotations

import datetime as dt
from typing import Dict, Mapping

import numpy as np

EPOCH = dt.date(1970, 1, 1)

#: dictionary codes, in the strings' sort order
RETURNFLAG = {"A": 0, "N": 1, "R": 2}
LINESTATUS = {"F": 0, "O": 1}
SHIPINSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
SHIPMODE = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]


def _day(iso: str) -> int:
    return (dt.date.fromisoformat(iso) - EPOCH).days


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of each part, in dollars (clause 4.2.3)."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def supplier(partkey: np.ndarray, i: np.ndarray, suppliers: int) -> np.ndarray:
    """PS_SUPPKEY of the part's ``i``-th supplier (clause 4.2.3)."""
    return (partkey + i * (suppliers // 4 + (partkey - 1) // suppliers)) % suppliers + 1


def orders(rng: np.random.Generator, n: int) -> "tuple[np.ndarray, np.ndarray]":
    """L_ORDERKEY and L_LINENUMBER of ``n`` rows: orders of 1-7 lines in
    turn (the last cut to fit), keyed as dbgen keys them, the first 8 of
    every 32 keys."""
    lines = rng.integers(1, 8, n // 4 + 8)
    while lines.sum() < n:
        lines = np.concatenate([lines, rng.integers(1, 8, len(lines))])
    ends = np.cumsum(lines)
    count = int(np.searchsorted(ends, n)) + 1
    order = np.repeat(np.arange(count, dtype=np.int64), lines[:count])[:n]
    starts = np.concatenate([[0], ends[:count - 1]])
    linenumber = np.arange(n) - starts[order] + 1
    return (order // 8) * 32 + order % 8 + 1, linenumber


def generate(config: Mapping, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The ``lineitem`` table of ``config``, drawn from ``seed``."""
    n = int(config["rows"])
    start, current = _day(config["start_date"]), _day(config["current_date"])
    end = _day(config["end_date"])

    fixed = np.random.default_rng(config["structure_seed"])
    orderdate = fixed.integers(start, end - 151 + 1, n)
    shipdate = orderdate + fixed.integers(1, 122, n)
    discount = fixed.integers(0, 11, n)
    quantity = fixed.integers(1, 51, n)

    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    shipdate, discount, quantity = shipdate[perm], discount[perm], quantity[perm]
    orderdate = orderdate[perm]
    parts = int(config["parts_per_sf"]) * int(config["scale_factor"])
    partkey = rng.integers(1, parts + 1, n)
    tax = rng.integers(0, 9, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returned = rng.integers(0, 2, n).astype(bool)

    returnflag = np.where(
        receiptdate <= current,
        np.where(returned, RETURNFLAG["R"], RETURNFLAG["A"]),
        RETURNFLAG["N"],
    )
    linestatus = np.where(shipdate > current, LINESTATUS["O"], LINESTATUS["F"])
    orderkey, linenumber = orders(rng, n)
    suppliers = int(config["suppliers_per_sf"]) * int(config["scale_factor"])
    table = {
        "l_orderkey": orderkey.astype(np.int32),
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": supplier(partkey, rng.integers(0, 4, n), suppliers).astype(np.int32),
        "l_linenumber": linenumber.astype(np.int8),
        "l_quantity": quantity.astype(np.int32),
        "l_extendedprice": (quantity * retail_price(partkey)).astype(np.float32),
        "l_discount": (discount / 100.0).astype(np.float32),
        "l_tax": (tax / 100.0).astype(np.float32),
        "l_shipdate": shipdate.astype(np.int32),
        "l_returnflag": returnflag.astype(np.int8),
        "l_linestatus": linestatus.astype(np.int8),
        "l_commitdate": (orderdate + rng.integers(30, 91, n)).astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": rng.integers(0, len(SHIPINSTRUCT), n).astype(np.int8),
        "l_shipmode": rng.integers(0, len(SHIPMODE), n).astype(np.int8),
    }
    return {"lineitem": {c: table[c] for c in config["tables"]["lineitem"]["columns"]}}
