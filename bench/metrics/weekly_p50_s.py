"""Median latency of the weekly requests (``"op": "weekly"`` in the mix: a
week of trips, most shards pruned), each from when it was due to its return."""
import statistics


def read(run):
    latencies = run.latencies_of("weekly")
    return statistics.median(latencies) if latencies else None
