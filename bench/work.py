"""What a statement asks of the device, from the statement alone.

The least time a chip could take for one query is the larger of its
operations over the chip's peak rate and its bytes over the chip's HBM
bandwidth.  Both are counted from the structured statement and the rows
of each of its tables that its own conjuncts keep, as if every conjunct
were pushed into the scan of the table it names; not from the program
that ran, so a change of implementation (kernel or jnp, predicates
pushed down or not) is held to the same number.

* bytes: for each table, the rows its scan hands over times the width
  of the columns it hands over (join keys included), each read once;
  plus the answer written once (keys at their width, aggregates at 4
  bytes);
* operations: one for each join key of each row handed over; then, per
  row that survives the joins, one for each group key (its slot), and
  for each aggregate the expression's arithmetic plus one add.

Peaks come from ``peaks.json``, keyed by JAX's ``device_kind``; a device
that is not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Mapping, Tuple

import numpy as np

from reference import expression_ops, input_columns, join_keys

PEAKS = Path(__file__).resolve().parent / "peaks.json"
AGG_BYTES = 4


class UnknownDevice(KeyError):
    """The peaks table has no entry for this device kind."""


def peaks(device_kind: str, path: Path = PEAKS) -> Dict[str, float]:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def handed_columns(stmt: Mapping) -> list:
    """Columns the scans hand to the device (WHERE is applied on the host):
    the answer's inputs and the join keys."""
    return list(dict.fromkeys(input_columns(stmt) + join_keys(stmt)))


def statement_work(stmt: Mapping, dtypes: Mapping[str, Mapping[str, np.dtype]],
                   scanned: Mapping[str, int], rows_in: int,
                   rows_out: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one statement.

    ``dtypes`` gives the columns of each of the statement's tables,
    ``scanned`` the rows of each that its own conjuncts keep, ``rows_in``
    the rows that survive every conjunct and join (those the group keys
    and aggregates see) and ``rows_out`` the rows of the answer."""
    owner = {c: t for t, cols in dtypes.items() for c in cols}
    width = {t: 0 for t in dtypes}
    for c in handed_columns(stmt):
        width[owner[c]] += np.dtype(dtypes[owner[c]][c]).itemsize
    probes = {t: 0 for t in dtypes}
    for c in join_keys(stmt):
        probes[owner[c]] += 1
    aggs = stmt.get("aggs", ())
    out_width = sum(np.dtype(dtypes[owner[k]][k]).itemsize for k in stmt.get("group_by", ()))
    out_width += AGG_BYTES * len(aggs)
    per_row = len(stmt.get("group_by", ())) + sum(
        (expression_ops(a["expr"]) if "expr" in a else 0) + 1 for a in aggs)
    ops = rows_in * per_row + sum(n * probes[t] for t, n in scanned.items())
    nbytes = sum(n * width[t] for t, n in scanned.items()) + rows_out * out_width
    return float(ops), float(nbytes)


def least_time(ops: float, nbytes: float, peak: Mapping[str, float]) -> float:
    """Seconds the chip needs at best for that work."""
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
