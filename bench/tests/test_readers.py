"""Each query's phases reach the request that made it, and the readers of
one operation type read only its requests."""
import statistics

import pytest

import harness
import tiny


class QueryExecuted:
    def __init__(self, ts, wall_s, scan_s):
        self.ts, self.wall_s, self.scan_s = ts, wall_s, scan_s
        self.parse_s = self.plan_s = self.exec_s = 0.0


def _request(op, called, end):
    return harness.Request(0, None, op, called, end, {}, called)


def test_each_event_reaches_the_call_that_published_it():
    offset = 1000.0
    # two calls return 20 us apart; the later one published first
    a, b = _request("weekly", 0.95, 1.0), _request("full", 0.2, 1.00002)
    c = _request("weekly", 1.5, 1.6)
    failed = harness.Request(0, None, "full", 1.0, 1.7, RuntimeError("lost"), 1.0)
    events = [QueryExecuted(offset + 0.999985, 0.79998, 2.0),
              QueryExecuted(offset + 0.99999, 0.04999, 1.0),
              QueryExecuted(offset + 1.59999, 0.0999, 3.0)]
    harness.attribute([c, failed, b, a], list(events), offset)
    assert (a.event, b.event, c.event, failed.event) == (events[1], events[0], events[2], None)


def test_readers_of_one_operation_read_only_its_requests():
    reqs = []
    for k, (op, scan) in enumerate([("weekly", 1.0), ("full", 9.0), ("weekly", 3.0),
                                    ("weekly", 2.0), ("full", 7.0)]):
        r = _request(op, float(k), float(k) + scan / 10)
        r.event = QueryExecuted(0.0, 0.0, scan / 1e3)
        reqs.append(r)
    run = harness.Measured("query", 1.0, 0.0, 5.0, reqs, [r.event for r in reqs])

    def read(name):
        return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(run)

    assert read("scan_ms.weekly") == pytest.approx(2.0)
    assert read("scan_ms.full") == pytest.approx(8.0)
    assert read("weekly_p50_s") == pytest.approx(0.2)
    assert read("weekly_tail_p90_s") == pytest.approx(0.28)
    assert read("full_p50_s") == pytest.approx(statistics.median([0.9, 0.7]))
    assert read("query_p50_s") == pytest.approx(0.3)
    assert read("device_idle.mix") is None  # untraced: nothing to read


def test_the_dashboard_reports_latency_per_operation():
    result = tiny.run("taxi.dashboard")
    assert set(result["metrics"]) == {"weekly_p50_s", "full_p50_s", "full_p90_s",
                                      "setup_s"}
    assert result["metrics"]["full_p50_s"]["value"] > 0
