"""The control of the comparison that decides ``correct``.

The reference is put in the program's place and computed one precision
below the one the configuration states (``reference.py``,
``precision="bf16"``): float inputs and row arithmetic in bfloat16,
integer aggregates carried through a bfloat16 accumulator.  Its answers
go through the same judgement as a run's; a comparison that reads the
control as correct is too loose to catch the program doing the same.

    python3 bench/control.py --workload tpch_sf1.q1 --seeds 11 12 13

prints, for each seed, the numbers compared beside their limits, as a
JSON line.  It needs no chip: the control is numpy.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Dict

sys.path[:0] = [str(Path(__file__).resolve().parent)]

import harness  # noqa: E402
import reference  # noqa: E402


def control_numbers(cell: harness.Cell, seed: int) -> Dict[str, float]:
    """The numbers compared, with the control's answers as the program's."""
    tables = cell.generate(seed)
    mix = cell.mix
    if mix["kind"] == "query":
        traffic = harness.Traffic(mix, seed)
        keys = traffic.every()
        got = harness.query_answers(traffic, keys, tables, precision="bf16")
        requests = [harness.Request(r, p, traffic.op(r), 0.0, 0.0,
                                    reference.head(got[(r, p)], traffic.statement(r)))
                    for r, p in keys]
        want = harness.query_answers(traffic, keys, tables)
        return harness.judge_queries(mix, traffic, requests, 0, want)[0]
    spec = mix["pipeline"]
    answers, verdicts = harness.pipeline_reference(spec, tables)
    low, low_verdicts = harness.pipeline_reference(spec, tables, precision="bf16")
    handle = SimpleNamespace(state=SimpleNamespace(name="SUCCESS"),
                             checks=low_verdicts, merged_commit="control")
    nodes = {n["name"]: n for n in spec["nodes"]}
    read_back = {name: reference.head(low[name], nodes[name]["statement"])
                 for name in spec["read_back"]}
    return harness.judge_runs(spec, [harness.Request(0, None, "run", 0.0, 0.0, handle)],
                              read_back, ["control"], answers, verdicts)[0]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    args = parser.parse_args()
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        numbers = control_numbers(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed, "compared": {
            k: {"value": v, "limit": cell.mix["limits"][k]} for k, v in numbers.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
