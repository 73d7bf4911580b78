"""What a statement asks of the device, from the statement alone.

The least time a chip could take for one query is the larger of its
operations over the chip's peak rate and its bytes over the chip's HBM
bandwidth.  Both are counted from the structured statement and the rows
that the scan handed to the device, not from the program that ran, so a
change of implementation (kernel or jnp) is held to the same number.

* bytes: every column handed over, read once at its width, plus the
  answer written once (keys at their width, aggregates at 4 bytes);
* operations: per row handed over, one for each group key (its slot),
  and for each aggregate the expression's arithmetic plus one add.

Peaks come from ``peaks.json``, keyed by JAX's ``device_kind``; a device
that is not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Mapping, Tuple

import numpy as np

from reference import expression_columns, expression_ops

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
    """Columns the scan hands to the device (WHERE is applied on the host)."""
    cols = list(stmt.get("group_by", ()))
    cols += [src for _, src in stmt.get("select", ())]
    for agg in stmt.get("aggs", ()):
        if "expr" in agg:
            cols += expression_columns(agg["expr"])
    return list(dict.fromkeys(cols))


def statement_work(stmt: Mapping, dtypes: Mapping[str, np.dtype],
                   rows_in: int, rows_out: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one statement over ``rows_in`` rows."""
    width = sum(np.dtype(dtypes[c]).itemsize for c in handed_columns(stmt))
    aggs = stmt.get("aggs", ())
    out_width = sum(np.dtype(dtypes[k]).itemsize for k in stmt.get("group_by", ()))
    out_width += AGG_BYTES * len(aggs)
    per_row = len(stmt.get("group_by", ())) + sum(
        (expression_ops(a["expr"]) if "expr" in a else 0) + 1 for a in aggs)
    return float(rows_in * per_row), float(rows_in * width + rows_out * out_width)


def least_time(ops: float, nbytes: float, peak: Mapping[str, float]) -> float:
    """Seconds the chip needs at best for that work."""
    return max(ops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
