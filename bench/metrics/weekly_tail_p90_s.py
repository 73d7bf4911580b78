"""90th percentile latency of the weekly requests (``"op": "weekly"`` in the
mix), each from when it was due to its return.  Nearly all of that tail is
weekly requests whose scan shares the host with a whole-table scan."""
import numpy as np


def read(run):
    latencies = run.latencies_of("weekly")
    return float(np.percentile(latencies, 90)) if latencies else None
