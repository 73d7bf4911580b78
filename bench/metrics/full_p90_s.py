"""90th percentile latency of the whole-table requests (``"op": "full"`` in
the mix), each from when it was due to its return."""
import numpy as np


def read(run):
    latencies = run.latencies_of("full")
    return float(np.percentile(latencies, 90)) if latencies else None
