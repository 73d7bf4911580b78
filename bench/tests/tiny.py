"""Tiny sizes of the benchmark's configurations, for tests on the CPU."""
import time

import harness

#: rows per configuration: enough for integer counts past 256, which
#: bfloat16 cannot hold, and for several shards a table
ROWS = {"taxi_2019": 90 * 2000, "tpch_sf1": 60_000}


#: offered rate of the query mixes, for the CPU's interpreted kernels
RATE_PER_S = 20.0


def cell(name: str) -> harness.Cell:
    config = "taxi_2019" if name.startswith("taxi.") else "tpch_sf1"
    c = harness.load_cell(name, config_overrides={"rows": ROWS[config]})
    if "rate_per_s" in c.mix:
        c.mix["rate_per_s"] = RATE_PER_S
    return c


def run(name: str, seed: int = 2**31 + 7, seconds: float = 1.0) -> dict:
    """One untraced run of a cell, the chip check skipped."""
    return harness.run_cell(cell(name), seed, seconds, False,
                            started=time.perf_counter(), require_chip=False)


CELLS = ["taxi.dashboard", "tpch_sf1.q1", "taxi.pipeline", "tpch_sf1.q6"]
