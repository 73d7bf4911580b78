"""Median ``QueryExecuted.exec_s`` of the weekly requests (``"op": "weekly"``):
the device program, its transfers and the copy of the answer back to the host."""
import statistics


def read(run):
    events = run.query_events("weekly")
    if not events:
        return None
    return 1e3 * statistics.median(e.exec_s for e in events)
