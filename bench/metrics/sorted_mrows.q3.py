"""Median ``QueryExecuted.sorted_rows``, in millions: the elements that a
query's compiled program sorts in one call, summed over its sort sites.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.sorted_rows for e in run.events_of("QueryExecuted") if hasattr(e, "sorted_rows")]
    if not values:
        return None
    return statistics.median(values) / 1e6
