"""The plain reference: the benchmark's statements evaluated with numpy.

It imports nothing of the program under test and reads only the tables
that a configuration generated from the seed.  A mix gives each
statement twice: as SQL text, which the program runs, and in the
structured form below, which this module evaluates::

    {"table": "lineitem",
     "where": [["l_shipdate", "<=", "1998-09-02"]],     # a conjunction
     "select": [["count", "passenger_count"]],           # projection only
     "group_by": ["l_returnflag", "l_linestatus"],
     "aggs": [{"name": "sum_qty", "fn": "sum", "expr": "l_quantity"}],
     "order_by": [["l_returnflag", "asc"]]}

Literals compare in the column's own type, as SQL compares a REAL
column with a literal; an ISO date is days since 1970-01-01.  Integer
aggregates are exact (int64); float aggregates are summed in float64
from the stored float32 values.

``precision="bf16"`` gives the control: the same statement computed in
bfloat16, the precision below the float32 that the configurations state:
every float input, every row's arithmetic and every float sum in
bfloat16 (a sum accumulated row by row in a bfloat16 accumulator), and
every integer aggregate narrowed to bfloat16, as a 16-bit float
accumulator would hold it.  A comparison that cannot tell the control
from the reference is too loose to catch the program doing the same.
"""
from __future__ import annotations

import ast
import datetime as dt
import operator
from typing import Dict, List, Mapping, Optional, Tuple

import ml_dtypes
import numpy as np

Table = Dict[str, np.ndarray]

EPOCH = dt.date(1970, 1, 1)
BF16 = np.dtype(ml_dtypes.bfloat16)

COMPARE = {
    "<": operator.lt, "<=": operator.le, ">": operator.gt, ">=": operator.ge,
    "==": operator.eq, "!=": operator.ne,
}
_ARITH = {ast.Add: np.add, ast.Sub: np.subtract, ast.Mult: np.multiply,
          ast.Div: np.true_divide}
#: dense group codes up to this many slots; sparser keys go through np.unique
_DENSE_GROUPS = 1 << 24


def days(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return (dt.date.fromisoformat(iso) - EPOCH).days


def substitute(value, params: Mapping[str, str]):
    """A literal with ``{name}`` placeholders filled from ``params``."""
    return value.format(**params) if isinstance(value, str) else value


def literal(value, dtype: np.dtype):
    """``value`` (number or ISO date) in the column's own type."""
    if isinstance(value, str):
        value = days(value)
    return np.dtype(dtype).type(value)


def expression_columns(expr: str) -> List[str]:
    """Column names an arithmetic expression reads."""
    return sorted({n.id for n in ast.walk(ast.parse(expr, mode="eval"))
                   if isinstance(n, ast.Name)})


def expression_ops(expr: str) -> int:
    """Arithmetic operations per row of an expression."""
    return sum(isinstance(n, ast.BinOp)
               for n in ast.walk(ast.parse(expr, mode="eval")))


def _evaluate(expr: str, cols: Table, precision: str) -> np.ndarray:
    def cast(a: np.ndarray) -> np.ndarray:
        if a.dtype.kind == "f":
            return a.astype(BF16 if precision == "bf16" else np.float64)
        return a.astype(np.int64)

    def const(v):
        if isinstance(v, float) or precision == "bf16":
            return np.asarray(v, BF16 if precision == "bf16" else np.float64)
        return np.int64(v)

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Name):
            return cast(cols[node.id])
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return const(node.value)
        if isinstance(node, ast.BinOp) and type(node.op) in _ARITH:
            return _ARITH[type(node.op)](ev(node.left), ev(node.right))
        raise ValueError(f"unsupported expression {ast.dump(node)}")

    return ev(ast.parse(expr, mode="eval"))


def _group_codes(keys: List[np.ndarray]) -> Tuple[np.ndarray, List[np.ndarray]]:
    """Dense group id of each row, ascending by key tuple, and the keys of
    each group."""
    n = len(keys[0])
    if n == 0:
        return np.zeros(0, np.int64), [k[:0] for k in keys]
    los = [int(k.min()) for k in keys]
    spans = [int(k.max()) - lo + 1 for k, lo in zip(keys, los)]
    if int(np.prod(spans, dtype=np.float64)) <= _DENSE_GROUPS:
        code = np.zeros(n, np.int64)
        for k, lo, span in zip(keys, los, spans):
            code = code * span + (k.astype(np.int64) - lo)
        present = np.flatnonzero(np.bincount(code, minlength=int(np.prod(spans))))
        slot = np.full(int(np.prod(spans)), -1, np.int64)
        slot[present] = np.arange(len(present))
        out, rest = [], present
        for k, lo, span in reversed(list(zip(keys, los, spans))):
            out.append((rest % span + lo).astype(k.dtype))
            rest = rest // span
        return slot[code], out[::-1]
    stacked = np.stack([k.astype(np.int64) for k in keys], axis=1)
    uniq, inv = np.unique(stacked, axis=0, return_inverse=True)
    return inv.reshape(-1), [uniq[:, i].astype(k.dtype) for i, k in enumerate(keys)]


def where_mask(stmt: Mapping, tables: Mapping[str, Table],
               params: Optional[Mapping[str, str]] = None) -> np.ndarray:
    """Rows of the statement's table that its WHERE keeps."""
    table = tables[stmt["table"]]
    mask = np.ones(len(next(iter(table.values()))), bool)
    for column, op, value in stmt.get("where", ()):
        col = table[column]
        mask &= COMPARE[op](col, literal(substitute(value, params or {}), col.dtype))
    return mask


def selected_rows(stmt: Mapping, tables: Mapping[str, Table],
                  params: Optional[Mapping[str, str]] = None) -> int:
    """How many rows the WHERE keeps: what the scan hands to the device,
    since every conjunct of the benchmark's statements is pushed down."""
    return int(where_mask(stmt, tables, params).sum())


def run_statement(stmt: Mapping, tables: Mapping[str, Table],
                  params: Optional[Mapping[str, str]] = None,
                  precision: str = "exact") -> Table:
    """Evaluate one structured statement over ``tables``."""
    table = tables[stmt["table"]]
    mask = where_mask(stmt, tables, params)
    cols = {c: v[mask] for c, v in table.items()} if not mask.all() else dict(table)
    if "select" in stmt:
        out = {alias: cols[src] for alias, src in stmt["select"]}
        return _order(out, stmt)
    keys = [cols[k] for k in stmt.get("group_by", ())]
    rows = int(mask.sum())
    if keys:
        code, key_out = _group_codes(keys)
        groups = len(key_out[0])
    else:  # a global aggregate: one group when any row survives
        code, key_out, groups = np.zeros(rows, np.int64), [], int(rows > 0)
    out: Table = dict(zip(stmt.get("group_by", ()), key_out))
    counts = np.bincount(code, minlength=groups).astype(np.int64)
    for agg in stmt.get("aggs", ()):
        name, fn = agg["name"], agg["fn"]
        if fn == "count":
            out[name] = _narrow(counts, precision)
            continue
        vals = _evaluate(agg["expr"], cols, precision)
        if precision == "bf16" and vals.dtype == BF16:
            total = _bf16_sums(vals, code, groups)
        else:  # float64 adds integers exactly below 2**53
            total = np.bincount(code, weights=vals.astype(np.float64), minlength=groups)
        if fn == "mean":
            out[name] = total / np.maximum(counts, 1)
        elif vals.dtype.kind in "iu":
            out[name] = _narrow(np.round(total).astype(np.int64), precision)
        else:
            out[name] = total
    return _order(out, stmt)


def _bf16_sums(vals: np.ndarray, code: np.ndarray, groups: int) -> np.ndarray:
    """Each group's sum, accumulated row by row in bfloat16."""
    order = np.argsort(code, kind="stable")
    counts = np.bincount(code, minlength=groups)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    total = np.zeros(groups, np.float64)
    present = counts > 0
    if present.any():
        sums = np.add.reduceat(vals[order], starts[present])
        total[present] = sums.astype(np.float64)
    return total


def _narrow(ints: np.ndarray, precision: str) -> np.ndarray:
    if precision != "bf16":
        return ints
    return ints.astype(np.float32).astype(BF16).astype(np.float64).astype(np.int64)


def _order(out: Table, stmt: Mapping) -> Table:
    by = stmt.get("order_by") or ()
    if not by or not out:
        return out
    perm = np.arange(len(next(iter(out.values()))))
    for column, direction in reversed(by):
        vals = out[column][perm]
        perm = perm[np.argsort(-vals if direction == "desc" else vals, kind="stable")]
    return {c: v[perm] for c, v in out.items()}


def run_expectation(spec: Mapping, tables: Mapping[str, Table]) -> bool:
    """``stat(column) <op> value`` over a table, as an audit node states it."""
    col = tables[spec["input"]][spec["column"]].astype(np.float64)
    stat = {"mean": np.mean, "sum": np.sum, "min": np.min, "max": np.max}[spec["stat"]]
    return bool(COMPARE[spec["op"]](stat(col), spec["value"]))


# ------------------------------------------------------------- comparison
def _canonical(out: Table, keys: List[str]) -> Table:
    """Rows sorted by ``keys`` (all key columns ascending): a canonical order
    for results whose row order SQL leaves open."""
    if not keys or not out:
        return out
    perm = np.lexsort([np.asarray(out[k]) for k in reversed(keys)])
    return {c: np.asarray(v)[perm] for c, v in out.items()}


def order_violations(got: Table, stmt: Mapping) -> int:
    """Adjacent row pairs of the program's answer that break ORDER BY."""
    by = stmt.get("order_by") or ()
    if not by or not got:
        return 0
    n = len(next(iter(got.values())))
    if n < 2:
        return 0
    bad = np.zeros(n - 1, bool)
    tied = np.ones(n - 1, bool)
    for column, direction in by:
        v = np.asarray(got[column]).astype(np.float64)
        step = v[1:] - v[:-1]
        if direction == "desc":
            step = -step
        bad |= tied & (step < 0)
        tied &= step == 0
    return int(bad.sum())


def compare(got: Mapping[str, np.ndarray], want: Table,
            stmt: Mapping) -> Tuple[int, float]:
    """How far a program's answer lies from the reference.

    Returns ``(wrong_ints, float_rel_err)``: the integer cells (keys,
    counts, integer sums) that differ, plus ORDER BY violations; and the
    largest relative error of a float cell.  A missing column or a wrong
    row count makes every reference row wrong.  Rows whose order SQL
    leaves open (no ORDER BY, or ties in it) are matched in key order.
    """
    if set(got) != set(want):
        return max(len(next(iter(want.values()), ())), 1), 0.0
    lengths = {len(v) for v in got.values()} | {len(v) for v in want.values()}
    if len(lengths) != 1:
        return max(len(next(iter(want.values()))), 1), 0.0
    wrong = order_violations(got, stmt)
    # group keys are unique per row; a projection is ordered by all columns
    keys = list(stmt.get("group_by", ())) or [a for a, _ in stmt.get("select", ())]
    g, w = _canonical(dict(got), keys), _canonical(dict(want), keys)
    rel = 0.0
    for column, ref in w.items():
        val = np.asarray(g[column])
        if ref.dtype.kind in "iub":
            wrong += int(np.count_nonzero(val.astype(np.int64) != ref.astype(np.int64)))
            continue
        err = np.abs(val.astype(np.float64) - ref) / np.maximum(np.abs(ref), 1e-30)
        err = np.where(np.isnan(err), np.inf, err)
        if len(err):
            rel = max(rel, float(err.max()))
    return wrong, rel
