"""Median latency of every ``Client.query`` sent in the window, over all
clients, each timed from the call to its return."""
import statistics


def read(run):
    if run.kind != "query" or not run.requests:
        return None
    return statistics.median(run.latencies)
