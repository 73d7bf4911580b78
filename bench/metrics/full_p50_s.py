"""Median latency of the whole-table requests (``"op": "full"`` in the mix:
every shard read), each from when it was due to its return."""
import statistics


def read(run):
    latencies = run.latencies_of("full")
    return statistics.median(latencies) if latencies else None
