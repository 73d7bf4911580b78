"""Median ``QueryExecuted.fetch_s``: the program's outputs copied back to
the host.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.fetch_s for e in run.events_of("QueryExecuted") if hasattr(e, "fetch_s")]
    if not values:
        return None
    return 1e3 * statistics.median(values)
