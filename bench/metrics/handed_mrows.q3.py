"""Median ``QueryExecuted.rows_handed``, in millions: the rows that a
query's scans hand to the device, summed over its tables, after the
conjuncts pushed into them.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.rows_handed for e in run.events_of("QueryExecuted") if hasattr(e, "rows_handed")]
    if not values:
        return None
    return statistics.median(values) / 1e6
