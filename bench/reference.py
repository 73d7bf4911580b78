"""The plain reference: the benchmark's statements evaluated with numpy.

It imports nothing of the program under test and reads only the tables
that a configuration generated from the seed.  A mix gives each
statement twice: as SQL text, which the program runs, and in the
structured form below, which this module evaluates::

    {"table": "lineitem",
     "joins": [{"table": "orders",   "on": ["l_orderkey", "o_orderkey"]},
               {"table": "customer", "on": ["o_custkey",  "c_custkey"]}],
     "where": [["c_mktsegment", "=", 1],                  # a conjunction
               ["l_shipdate", ">", "1995-03-15"]],
     "select": [["count", "passenger_count"]],            # projection only
     "group_by": ["l_orderkey", "o_orderdate"],
     "aggs": [{"name": "revenue", "fn": "sum",
               "expr": "l_extendedprice * (1 - l_discount)"}],
     "order_by": [["revenue", "desc"], ["o_orderdate", "asc"]],
     "limit": 10}

``joins`` and ``limit`` are optional.  Joins are inner equi-joins,
applied in order; each ``on`` pair names a column already in the joined
relation and a column of the joined table, and every match is kept, as
SQL keeps it (many-to-one and many-to-many alike).  Column names are
unique across a statement's tables: a name that two of them hold is an
error, not a guess.  Each WHERE conjunct names one column and is applied
to the table that holds it, before the joins, which for inner joins
gives the same rows.  ``limit`` cuts the answer after ``order_by``:
``run_statement`` returns the whole ordered answer and ``head`` cuts it,
so that ``compare`` can tell a near-tie at the cut from a wrong row.

Literals compare in the column's own type, as SQL compares a REAL
column with a literal; an ISO date is days since 1970-01-01.  Integer
aggregates are exact (int64); float aggregates are summed in float64
from the stored float32 values.

``precision="bf16"`` gives the control: the same statement computed in
bfloat16, the precision below the float32 that the configurations state:
every float input, every row's arithmetic and every float sum in
bfloat16 (a sum accumulated row by row in a bfloat16 accumulator), and
every integer aggregate narrowed to bfloat16, as a 16-bit float
accumulator would hold it.  Joins and keys are exact in both.  A
comparison that cannot tell the control from the reference is too loose
to catch the program doing the same.
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
    "==": operator.eq, "=": operator.eq, "!=": operator.ne,
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


def rows(table: Mapping[str, np.ndarray]) -> int:
    """Rows of a table or an answer."""
    return len(next(iter(table.values()), ()))


def statement_tables(stmt: Mapping) -> List[str]:
    """The statement's tables: its own, then each joined one in order."""
    return [stmt["table"]] + [j["table"] for j in stmt.get("joins", ())]


def input_columns(stmt: Mapping) -> List[str]:
    """Columns the answer is computed from: the group keys, the projection
    and the aggregates' inputs."""
    cols = list(stmt.get("group_by", ()))
    cols += [src for _, src in stmt.get("select", ())]
    for agg in stmt.get("aggs", ()):
        if "expr" in agg:
            cols += expression_columns(agg["expr"])
    return list(dict.fromkeys(cols))


def join_keys(stmt: Mapping) -> List[str]:
    """The columns of every ``on`` pair, in order."""
    return [c for join in stmt.get("joins", ()) for c in join["on"]]


def named_columns(stmt: Mapping) -> List[str]:
    """Every column the statement names: in WHERE, ON and its inputs."""
    cols = [column for column, _, _ in stmt.get("where", ())]
    return list(dict.fromkeys(cols + join_keys(stmt) + input_columns(stmt)))


def owners(stmt: Mapping, tables: Mapping[str, Table]) -> Dict[str, str]:
    """The table of the statement that holds each column it names.  A
    name that none of them holds, or more than one, is an error."""
    names = statement_tables(stmt)
    found = {}
    for column in named_columns(stmt):
        holders = [t for t in names if column in tables[t]]
        if not holders:
            raise KeyError(f"no table of {names} has the column {column!r}")
        if len(holders) > 1:
            raise ValueError(f"the column {column!r} is ambiguous: {holders} all have it")
        found[column] = holders[0]
    return found


def where_masks(stmt: Mapping, tables: Mapping[str, Table],
                params: Optional[Mapping[str, str]] = None) -> Dict[str, np.ndarray]:
    """Rows of each of the statement's tables that the conjuncts on its own
    columns keep."""
    owner = owners(stmt, tables)
    masks = {t: np.ones(rows(tables[t]), bool) for t in statement_tables(stmt)}
    for column, op, value in stmt.get("where", ()):
        col = tables[owner[column]][column]
        masks[owner[column]] &= COMPARE[op](
            col, literal(substitute(value, params or {}), col.dtype))
    return masks


def table_rows(stmt: Mapping, tables: Mapping[str, Table],
               params: Optional[Mapping[str, str]] = None) -> Dict[str, int]:
    """Rows of each of the statement's tables that the conjuncts on its own
    columns keep: what its scan hands to the device when every conjunct
    is pushed into the scan of the table it names."""
    return {t: int(m.sum()) for t, m in where_masks(stmt, tables, params).items()}


def selected_rows(stmt: Mapping, tables: Mapping[str, Table],
                  params: Optional[Mapping[str, str]] = None) -> int:
    """Rows that every conjunct and join keep: those the group keys and
    aggregates see.  For a statement of one table, the rows its WHERE
    keeps."""
    return _joined(stmt, tables, params, ())[1]


def _matches(left: np.ndarray, right: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Every pair of rows ``(i, j)`` with ``left[i] == right[j]``: the
    right side sorted, both bounds of each left key searched, and each
    left row repeated once per match."""
    order = np.argsort(right, kind="stable")
    ranked = right[order]
    lo = np.searchsorted(ranked, left, side="left")
    counts = np.searchsorted(ranked, left, side="right") - lo
    li = np.repeat(np.arange(len(left)), counts)
    first = np.repeat(lo - (np.cumsum(counts) - counts), counts)
    return li, order[first + np.arange(len(li))]


def _joined(stmt: Mapping, tables: Mapping[str, Table],
            params: Optional[Mapping[str, str]],
            columns) -> Tuple[Table, int]:
    """The statement's tables, each cut to the rows its own conjuncts keep,
    joined: ``columns`` of the joined relation and its number of rows."""
    owner = owners(stmt, tables)
    masks = where_masks(stmt, tables, params)
    carried = list(dict.fromkeys(list(columns) + join_keys(stmt)))

    def kept(table: str) -> Table:
        mask, data = masks[table], tables[table]
        cols = [c for c in carried if owner[c] == table]
        if mask.all():
            return {c: data[c] for c in cols}
        return {c: data[c][mask] for c in cols}

    rel, n = kept(stmt["table"]), int(masks[stmt["table"]].sum())
    joined = [stmt["table"]]
    for join in stmt.get("joins", ()):
        left_on, right_on = join["on"]
        if owner[left_on] not in joined or owner[right_on] != join["table"]:
            raise ValueError(f"JOIN {join['table']} ON {left_on} = {right_on}: the first "
                             f"column must come from {joined}, the second from {join['table']}")
        right = kept(join["table"])
        li, ri = _matches(rel[left_on], right[right_on])
        rel = {**{c: v[li] for c, v in rel.items()}, **{c: v[ri] for c, v in right.items()}}
        n = len(li)
        joined.append(join["table"])
    return rel, n


def run_statement(stmt: Mapping, tables: Mapping[str, Table],
                  params: Optional[Mapping[str, str]] = None,
                  precision: str = "exact") -> Table:
    """Evaluate one structured statement over ``tables``: its whole answer,
    in ORDER BY's order and before LIMIT (``head`` cuts it)."""
    cols, n = _joined(stmt, tables, params, input_columns(stmt))
    if "select" in stmt:
        out = {alias: cols[src] for alias, src in stmt["select"]}
        return _order(out, stmt)
    keys = [cols[k] for k in stmt.get("group_by", ())]
    if keys:
        code, key_out = _group_codes(keys)
        groups = len(key_out[0])
    else:  # a global aggregate: one group when any row survives
        code, key_out, groups = np.zeros(n, np.int64), [], int(n > 0)
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


def kept_rows(stmt: Mapping, n: int) -> int:
    """Rows of an answer of ``n`` rows that its LIMIT keeps."""
    return min(int(stmt["limit"]), n) if "limit" in stmt else n


def head(out: Table, stmt: Mapping) -> Table:
    """The answer as SQL gives it: the ordered answer cut at LIMIT."""
    if "limit" not in stmt:
        return out
    k = kept_rows(stmt, rows(out))
    return {c: v[:k] for c, v in out.items()}


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


def _row_keys(stmt: Mapping) -> List[str]:
    """Columns that name an answer's row: group keys are unique per row,
    and a projection is keyed by all its columns."""
    return list(stmt.get("group_by", ())) or [a for a, _ in stmt.get("select", ())]


def _keys_of(out: Mapping[str, np.ndarray], keys: List[str]) -> List[tuple]:
    if not keys:  # a global aggregate's rows are named by their place
        return [(i,) for i in range(rows(out))]
    return list(zip(*(np.asarray(out[k]).tolist() for k in keys)))


def _at_the_cut(got: Mapping[str, np.ndarray], want: Table, stmt: Mapping,
                rel_err: float) -> Tuple[List[int], List[int], int]:
    """Pair each row of a program's answer cut at LIMIT with the row of the
    reference's whole ordered answer that has its key.

    A row whose key is among the reference's first ``k`` rows (``k`` the
    rows the answer keeps) pairs with it.  One whose key lies further
    down pairs only as a near-tie at the cut: its first ORDER BY value
    within ``rel_err`` of the reference's ``k``-th row's.  Any other row
    is wrong, and so is the kept reference row it displaced; a kept
    reference row that the answer lacks is wrong too, unless it lies
    within ``rel_err`` of the cut itself.  Returns the paired rows of the
    answer and of the reference, and how many rows are wrong."""
    k = rows(got)
    index: Dict[tuple, int] = {}
    for j, key in enumerate(_keys_of(want, _row_keys(stmt))):
        index.setdefault(key, j)
    by = stmt.get("order_by") or ()
    if by and k:
        first = np.asarray(want[by[0][0]]).astype(np.float64)
        near = np.abs(first - first[k - 1]) <= rel_err * abs(first[k - 1])
    else:  # without ORDER BY any rows of the answer will do
        near = np.ones(rows(want), bool)
    paired: Dict[int, int] = {}
    bad = 0
    for i, key in enumerate(_keys_of(got, _row_keys(stmt))):
        j = index.get(key)
        if j is None or j in paired or (j >= k and not near[j]):
            bad += 1
            continue
        paired[j] = i
    far = sum(1 for j in range(k) if j not in paired and not near[j])
    return list(paired.values()), list(paired), bad + max(bad, far)


def compare(got: Mapping[str, np.ndarray], want: Table,
            stmt: Mapping, tie_rel_err: float = 0.0) -> Tuple[int, float]:
    """How far a program's answer lies from the reference's whole ordered
    answer ``want`` (``run_statement``).

    Returns ``(wrong_ints, float_rel_err)``: the integer cells (keys,
    counts, integer sums) that differ, plus ORDER BY violations; and the
    largest relative error of a float cell.  A missing column or a wrong
    row count makes every reference row that SQL's answer keeps wrong.
    Rows whose order SQL leaves open (no ORDER BY, or ties in it) are
    matched in key order.  Under LIMIT the answer keeps ``kept_rows``
    rows, each row's key must be in the whole answer, and a row from
    beyond the cut stands in for a kept one only as a near-tie, within
    ``tie_rel_err`` (``_at_the_cut``); each row that does not counts as
    wrong, as does the row it displaced.
    """
    n = kept_rows(stmt, rows(want))
    if set(got) != set(want) or {len(v) for v in got.values()} != {n}:
        return max(n, 1), 0.0
    wrong = order_violations(got, stmt)
    if "limit" in stmt:
        mine, theirs, bad = _at_the_cut(got, want, stmt, tie_rel_err)
        got = {c: np.asarray(v)[mine] for c, v in got.items()}
        want = {c: v[theirs] for c, v in want.items()}
        wrong += bad
    keys = _row_keys(stmt)
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
