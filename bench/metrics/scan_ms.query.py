"""Median ``QueryExecuted.scan_s``: shard reads, host filter, concat and
the enqueue of the copy to the device."""
import statistics


def read(run):
    events = run.events_of("QueryExecuted")
    if not events:
        return None
    return 1e3 * statistics.median(e.scan_s for e in events)
