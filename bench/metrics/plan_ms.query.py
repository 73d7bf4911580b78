"""Median of ``QueryExecuted.parse_s + plan_s``: parse, plan and route."""
import statistics


def read(run):
    events = run.events_of("QueryExecuted")
    if not events:
        return None
    return 1e3 * statistics.median(e.parse_s + e.plan_s for e in events)
