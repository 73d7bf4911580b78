"""Median ``QueryExecuted.parse_s + plan_s`` of the weekly requests (``"op": "weekly"``):
parse, plan and route."""
import statistics


def read(run):
    events = run.query_events("weekly")
    if not events:
        return None
    return 1e3 * statistics.median(e.parse_s + e.plan_s for e in events)
