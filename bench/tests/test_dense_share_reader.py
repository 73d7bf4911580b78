"""The reader of the group-by path counter: the share of the window's
queries whose group-by ran dense, and nothing from a program whose
events lack the field."""
import pytest

import harness


class QueryExecuted:
    def __init__(self, **fields):
        self.__dict__.update(fields)


def _read(run):
    return harness.load_module(harness.BENCH / "metrics" / "dense_share.query.py").read(run)


def _query_run(events):
    reqs = []
    for k, event in enumerate(events):
        r = harness.Request(0, None, "q1", float(k), float(k) + 0.1, {}, float(k))
        r.event = event
        reqs.append(r)
    return harness.Measured("query", 1.0, 0.0, 5.0, reqs, events)


@pytest.mark.parametrize("paths, want", [
    (["dense", "dense", "sort", "kernel"], 50.0),
    (["dense"] * 3, 100.0),
    (["sort", "kernel", ""], 0.0),
])
def test_dense_share_reads_the_group_path_of_the_windows_queries(paths, want):
    run = _query_run([QueryExecuted(group_path=p) for p in paths])
    assert _read(run) == pytest.approx(want)


def test_dense_share_reads_nothing_without_the_counter():
    assert _read(_query_run([QueryExecuted(scan_s=0.1)])) is None


def test_dense_share_is_reported_in_its_cells():
    declared = {m["name"]: m for m in harness.load_cell("tpch_sf1.q1").per_layer}
    assert declared["dense_share.query"]["source"] == "program_counter"
    for cell in declared["dense_share.query"]["workloads"]:
        traced = {m["name"] for m in harness.load_cell(cell).metrics(True)}
        assert "dense_share.query" in traced
