"""Sweep the offered rate of a query cell, to find the highest rate the
system sustains; a cell's mix then fixes its rate below that.

    python3 bench/sweep.py --workload taxi.dashboard --seed 5 --seconds 20 --rates 1 2 4 8

Set-up is made once; then one open-loop window per rate, each printed as
a JSON line: requests, and for each operation type the medians of the
first and the second half of the window (a backlog that grows shows as a
second half slower than the first) and the 90th percentile; how long
past the close the last request returned, and how late the sender ran.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

STARTED = time.perf_counter()
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    args = parser.parse_args()
    cell = harness.load_cell(args.workload)
    harness.pin_compile_cache()
    harness.check_chips(cell.chips)
    import repro

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    with tempfile.TemporaryDirectory(prefix="bench_") as tmp, \
            repro.Client(Path(tmp) / "lake") as client:
        traffic, _ = harness.prepare(cell, cell.generate(args.seed), args.seed,
                                     client, STARTED, log)
        workers = harness.Workers(client, traffic, int(cell.mix["workers"]), False)
        for rate in args.rates:
            t_open, t_close, reqs, hung, late = harness._query_window(
                workers, rate, args.seconds)
            reqs.sort(key=lambda r: r.start)
            by_op = {}
            for op in sorted({r.op for r in reqs}):
                lat = [r.end - r.start for r in reqs if r.op == op]
                half = len(lat) // 2
                by_op[op] = {
                    "requests": len(lat),
                    "p50_first_half_s": statistics.median(lat[:half]) if half else None,
                    "p50_second_half_s": statistics.median(lat[half:]),
                    "p90_s": float(np.percentile(lat, 90)),
                }
            print(json.dumps({
                "workload": args.workload, "rate_per_s": rate, "requests": len(reqs),
                "hung": hung, "errors": sum(isinstance(r.answer, BaseException) for r in reqs),
                "by_op": by_op, "drain_s": t_close - (t_open + args.seconds),
                "sender_late_s": late,
            }), flush=True)
        workers.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
