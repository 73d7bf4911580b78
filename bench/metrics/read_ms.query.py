"""Median ``QueryExecuted.read_s``: shard reads, host filter and concat,
inside ``scan_s``.

A program whose events lack the field reports nothing."""
import statistics


def read(run):
    values = [e.read_s for e in run.events_of("QueryExecuted") if hasattr(e, "read_s")]
    if not values:
        return None
    return 1e3 * statistics.median(values)
