"""The readers of the query and stage phases: each reads the median of
its field in ms, from its operation type's requests, and reads nothing
from a program whose events lack the field."""
import pytest

import harness


class QueryExecuted:
    def __init__(self, **phases):
        self.__dict__.update(phases)


class StageFinished(QueryExecuted):
    pass


def _read(name, run):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(run)


def _query_run(rows):
    """One request per ``(op, phases)`` of ``rows``."""
    reqs = []
    for k, (op, phases) in enumerate(rows):
        r = harness.Request(0, None, op, float(k), float(k) + 0.1, {}, float(k))
        r.event = QueryExecuted(**phases)
        reqs.append(r)
    return harness.Measured("query", 1.0, 0.0, 5.0, reqs, [r.event for r in reqs])


def _phases(read, copy, device, fetch):
    return {"read_s": read / 1e3, "copy_s": copy / 1e3,
            "device_s": device / 1e3, "fetch_s": fetch / 1e3}


DASHBOARD = [("weekly", _phases(30, 1, 2, 0.1)), ("full", _phases(250, 12, 18, 0.2)),
             ("weekly", _phases(34, 1, 2, 0.1)), ("weekly", _phases(32, 1, 2, 0.1)),
             ("full", _phases(270, 14, 18, 0.2))]
TPCH = [("q1", _phases(400, 20, 2700, 90)), ("q1", _phases(420, 22, 2800, 110)),
        ("q1", _phases(410, 21, 2750, 100))]


@pytest.mark.parametrize("name, rows, want", [
    ("read_ms.weekly", DASHBOARD, 32.0),
    ("read_ms.full", DASHBOARD, 260.0),
    ("copy_ms.full", DASHBOARD, 13.0),
    ("read_ms.query", TPCH, 410.0),
    ("copy_ms.query", TPCH, 21.0),
    ("device_ms.query", TPCH, 2750.0),
    ("fetch_ms.query", TPCH, 100.0),
])
def test_query_phase_readers(name, rows, want):
    assert _read(name, _query_run(rows)) == pytest.approx(want)
    # a program without the phases: the requests' events lack the fields
    bare = _query_run([(op, {"scan_s": 0.1}) for op, _ in rows])
    assert _read(name, bare) is None


@pytest.mark.parametrize("name, want", [
    ("read_ms.run", 200.0), ("device_ms.run", 880.0), ("write_ms.run", 110.0),
])
def test_stage_phase_readers(name, want):
    events = [StageFinished(read_s=r / 1e3, device_s=d / 1e3, write_s=w / 1e3)
              for r, d, w in [(190, 870, 100), (200, 880, 110), (230, 900, 120)]]
    run = harness.Measured("run", 1.0, 0.0, 5.0, [], events)
    assert _read(name, run) == pytest.approx(want)
    bare = harness.Measured("run", 1.0, 0.0, 5.0, [], [StageFinished(exec_s=1.2)])
    assert _read(name, bare) is None


def test_every_phase_reader_is_reported_in_its_cells():
    declared = {m["name"]: m for m in harness.load_cell("taxi.dashboard").per_layer}
    names = ["read_ms.weekly", "read_ms.full", "copy_ms.full", "read_ms.query",
             "copy_ms.query", "device_ms.query", "fetch_ms.query", "read_ms.run",
             "device_ms.run", "write_ms.run"]
    for name in names:
        assert (harness.BENCH / "metrics" / f"{name}.py").exists()
        assert declared[name]["source"] == "program_span"
        for cell in declared[name]["workloads"]:
            traced = {m["name"] for m in harness.load_cell(cell).metrics(True)}
            assert name in traced
