"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload taxi.dashboard --seed 7 --seconds 40 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number compared with the
reference beside its limit.  The same numbers are the last lines of
standard error.  Exits 2, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for, and where the program under test is
not beside the benchmark.
"""
import time

STARTED = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    import harness

    harness.pin_compile_cache()
    try:
        import jax  # noqa: F401

        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: cannot import the program under test: {e}", file=sys.stderr)
        return 2
    return harness.main(started=STARTED)


if __name__ == "__main__":
    sys.exit(main())
