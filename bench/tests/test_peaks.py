"""The peaks table, and the refusal to measure without a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import work

ROOT = Path(__file__).resolve().parents[2]


def test_known_device_has_its_peaks():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peak["source"]


def test_an_unknown_device_is_an_error():
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v9 imaginary")


def test_least_time_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(ops=50.0, nbytes=100.0, peak=peak) == 10.0
    assert work.least_time(ops=5000.0, nbytes=100.0, peak=peak) == 50.0


def test_statement_work_counts_handed_columns_and_answer():
    stmt = {"table": "t", "where": [["d", ">=", 1]], "group_by": ["k"],
            "aggs": [{"name": "n", "fn": "count"},
                     {"name": "s", "fn": "sum", "expr": "v * (1 - w)"}]}
    dtypes = {"t": {"k": "int8", "v": "float32", "w": "float32", "d": "int32"}}
    ops, nbytes = work.statement_work(stmt, dtypes, {"t": 1000}, rows_in=1000, rows_out=3)
    # per row: one key slot, one count add, two arithmetic ops + one add
    assert ops == 1000 * (1 + 1 + 3)
    # d is filtered on the host and never handed over
    assert nbytes == 1000 * (1 + 4 + 4) + 3 * (1 + 4 + 4)


def test_statement_work_counts_every_table_of_a_join():
    stmt = {"table": "l",
            "joins": [{"table": "o", "on": ["l_ok", "o_ok"]},
                      {"table": "c", "on": ["o_ck", "c_ck"]}],
            "where": [["c_seg", "=", 1], ["l_ship", ">", 5]],
            "group_by": ["l_ok", "o_date"],
            "aggs": [{"name": "rev", "fn": "sum", "expr": "l_price * (1 - l_disc)"}],
            "limit": 10}
    dtypes = {"l": {"l_ok": "int32", "l_price": "float32", "l_disc": "float32",
                    "l_ship": "int32"},
              "o": {"o_ok": "int32", "o_ck": "int32", "o_date": "int32"},
              "c": {"c_ck": "int32", "c_seg": "int8"}}
    ops, nbytes = work.statement_work(stmt, dtypes, {"l": 600, "o": 150, "c": 20},
                                      rows_in=90, rows_out=10)
    # a probe per join key of each row handed over (l_ok; o_ok, o_ck; c_ck),
    # then two key slots, two arithmetic ops and one add per joined row
    assert ops == 600 * 1 + 150 * 2 + 20 * 1 + 90 * (2 + 2 + 1)
    # l_ship and c_seg are filtered on the host and never handed over
    assert nbytes == 600 * (4 + 4 + 4) + 150 * (4 + 4 + 4) + 20 * 4 + 10 * (4 + 4 + 4)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "taxi.dashboard",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return True
        except ValueError:
            pass
    return False


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_run_without_the_program_exits_nonzero_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
