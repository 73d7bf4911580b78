"""Tiny sizes of the benchmark's configurations, for tests on the CPU.

Every workload of ``BENCHMARK.json`` runs here.  A configuration states
its tiny sizes in its own JSON, under an optional ``"tiny"`` key: the
keys of its sizes that the tiny run overrides.  The two oldest keep
theirs in ``ROWS``.
"""
import json
import time

import harness

#: rows per configuration: enough for integer counts past 256, which
#: bfloat16 cannot hold, and for several shards a table
ROWS = {"taxi_2019": 90 * 2000, "tpch_sf1": 60_000}


#: offered rate of the query mixes, for the CPU's interpreted kernels
RATE_PER_S = 20.0

SPEC = json.loads(harness.BENCHMARK.read_text())


def sizes(config: str) -> dict:
    """The overrides that make a configuration tiny."""
    if config in ROWS:
        return {"rows": ROWS[config]}
    entry = next(c for c in SPEC["configs"] if c["name"] == config)
    return json.loads((harness.ROOT / entry["file"]).read_text())["tiny"]


def cell(name: str) -> harness.Cell:
    config = next(w["config"] for w in SPEC["workloads"] if w["name"] == name)
    c = harness.load_cell(name, config_overrides=sizes(config))
    if "rate_per_s" in c.mix:
        c.mix["rate_per_s"] = RATE_PER_S
    return c


def run(name: str, seed: int = 2**31 + 7, seconds: float = 1.0) -> dict:
    """One untraced run of a cell, the chip check skipped."""
    return harness.run_cell(cell(name), seed, seconds, False,
                            started=time.perf_counter(), require_chip=False)


CELLS = [w["name"] for w in SPEC["workloads"]]
