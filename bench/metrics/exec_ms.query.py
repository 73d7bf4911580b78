"""Median ``QueryExecuted.exec_s``: the device program, its transfers and
the copy of the answer back to the host."""
import statistics


def read(run):
    events = run.events_of("QueryExecuted")
    if not events:
        return None
    return 1e3 * statistics.median(e.exec_s for e in events)
