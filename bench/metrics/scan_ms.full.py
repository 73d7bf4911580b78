"""Median ``QueryExecuted.scan_s`` of the whole-table requests (``"op": "full"``):
shard reads, host filter, concat and the enqueue of the copy to the device."""
import statistics


def read(run):
    events = run.query_events("full")
    if not events:
        return None
    return 1e3 * statistics.median(e.scan_s for e in events)
